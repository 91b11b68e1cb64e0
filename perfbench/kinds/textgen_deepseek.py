"""The text-generation kind for a DeepSeek-V2-class stack: the ``textgen``
kind's txt2txt jobs (``perfbench/kinds/textgen.py``: a prompt prefilled
in chunks, every row sampled in one scan, a JSON text artifact back)
served by ``chiaswarm_tpu/models/deepseek.py``. Owns
``perfbench/deepseekref.py`` (the plain float32 decoder).

From ``textgen`` comes what does not know the model: the unit of work
``[prompt tokens, new tokens, rows]``, the vocabulary of base-26 words,
the job, the artifact, the served rows and the mix's rules. This module
holds what does: the configuration in the program's terms, the seeded
weights and their scales, the registry, the reference, the work of a job
in operations and bytes. ``check`` and ``control`` are ``textgen``'s
restated, because those call their own module's reference (folding the
two is a ``benchmark`` PR's).

What decides ``correct``, as in the Ling cell: the served
``token_logprobs`` of the first and the last row of a sample of the
window's jobs against the reference run teacher-forced over the prompt
and the served tokens:

    logprob_gap        = max    |served token_logprob - reference's|
    logprob_gap_median = median |served token_logprob - reference's|

The widest gap is set by the router (the 6th and 7th best of the kept
groups' probabilities lie closer than a bfloat16 hidden state moves
them, so program and reference now and then choose another expert, in
ANY precision below float32) and by a softmax that is peaked on purpose
(a near-tie between two keys resolved the other way); the median leaves
that tail out and tells the precisions apart. Every layer of this stack
is latent attention, so the comparison has to see that layer (PERF.md
question 11d: with fan-in weights over 16,384 near-equal scores it adds
next to nothing, and no fault in it moves either number): ``GAINS``
below; and it has to see the one state the decode keeps, each row's
suffix of latents: ``SHARED`` and ``BLIND`` below. The limits are in the configuration's file with the readings they
were set from; the planted faults are in PERF.md (PR 33).
"""

from __future__ import annotations

import math
import random

from perfbench import compare
from perfbench.kinds.textgen import (  # noqa: F401
    PROGRAM_MODULES,
    ROWS,
    UNIT,
    check_mix,
    decode_artifact,
    ids_of,
    job,
    job_size,
    served_rows,
    word,
)

# ---- the configuration's sizes, in the program's terms -------------------


def deepseek_config(config: dict):
    """The program's ``DeepseekConfig`` of the configuration's file:
    every width as published, the experts and the vocabulary as held."""
    import dataclasses

    from chiaswarm_tpu.models.deepseek import DeepseekConfig, YarnScaling

    names = {f.name for f in dataclasses.fields(DeepseekConfig)}
    sizes = {k: v for k, v in config.items()
             if k in names and k not in ("n_routed_experts", "experts_held",
                                         "rope_scaling")}
    yarn = {f.name: config["rope_scaling"][f.name]
            for f in dataclasses.fields(YarnScaling)}
    return DeepseekConfig(
        n_routed_experts=int(config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"])),
        experts_held=tuple(config["experts_held"]),
        rope_scaling=YarnScaling(**yarn),
        dtype=config["serving"]["dtype"], **sizes)


# ---- weights and registry ------------------------------------------------

#: leaf name -> (mean, standard deviation); a kernel not listed is
#: fan-in scaled, a name ending in ``norm`` is ones. The embedding's
#: mean is the shared component of ``SHARED`` below.
LEAVES = {"embed": (0.2, 1.0)}

#: leaf name -> factor on its fan-in scale. Fan-in weights give a head
#: scores of standard deviation 1.59 (``s`` = 0.1147 over 192 unit
#: products), which over 16,384 keys is a softmax of ~1,300 effective
#: keys whose read-out is a hundredth of the residual: a layer the
#: comparison cannot see. ``wuq`` x 1.64 puts the scores at 2.6, ~20-30
#: effective keys a head, as peaked as a trained head and still many
#: keys (one key alone would turn every near-tie into a flipped
#: read-out). It scales the nope and the rope part of the query alike,
#: so the rotary part keeps its third of a score and a wrong position
#: shows. ``wo`` x 3 makes the five attention blocks add about as much
#: to the residual as the five MLPs do (~0.5 a layer each), as in a
#: trained stack, so that a fault in one layer's read-out moves the
#: logits. The router stays fan-in scaled: its logits have unit
#: deviation, the top of 160 probabilities is ~0.05 (a weight of ~0.7
#: after the factor 16), and 16 rows x 6 choices hit 17.6-18.6 of the
#: 40 held experts a layer a step over seeds (``moe_experts_hit.lat``).
GAINS = {"wuq": 1.64, "wo": 3.0}

#: leaf name -> size of a shared component: ``size / fan_in`` added to
#: every weight of the named columns, so that a kernel carries the mean
#: of its input into each of them. Independent weights know no recency:
#: a decoded token's own suffix is at most 64 of 16,448 keys and gets
#: 0.2% of a head's softmax, so a suffix cache that is never written
#: moved neither number of the comparison (PERF.md, PR 33), in a stack
#: whose only state is that cache. A trained stack attends to the
#: tokens just behind the query. Here every token's embedding has the
#: same small mean (``LEAVES``: 0.2 beside a unit deviation), ``wdq``
#: carries it into the query's bottleneck, and the ROPE columns of
#: ``wuq`` and of ``wdkv`` carry it into q_r and k_r. Both then hold one
#: and the same vector before the rotation, and their product after it
#: is ``sum_i cos((m - n) f_i)`` over YaRN's 32 frequencies: largest at
#: distance 0, down by a tenth at distance 2, by a half at a few
#: hundred. With these sizes the bias is ~20 in a score at distance 0
#: against ~6 far away, and a head puts a tenth to a half of its softmax
#: on the last few tokens (the rest on the prompt, by content as
#: before). ``wdq`` stays small and ``wuq`` large: a bottleneck that is
#: mostly shared would make every row ask the prompt the same question.
#: The residual's variance grows by about one a layer while the shared
#: mean stays, so a normed input's mean falls as ``(1 + layer)^-0.5``;
#: the two kernels that read the normed input (``wdq``, ``wdkv``) grow
#: their term by ``(1 + layer)^0.5`` to hold the bias level over the
#: five layers.
SHARED = {"wdq": 1.0, "wuq": 8.0, "wdkv": 9.0}

#: kernels that read the normed residual: each column sums to zero over
#: its fan-in, so the residual's shared mean moves nothing through them
#: and only the terms of ``SHARED`` read it. Left to leak, it reaches
#: every row's router alike (the values carry it at full weight while
#: their token parts average out over the keys), the rows lean to the
#: same groups, and the held experts hit a layer a step fall from 16-18
#: to 10-15 with the seed: ``job_p50_s`` then spreads by 2.7%, against
#: 0.2% with them (PERF.md, PR 33).
BLIND = ("wdq", "wdkv", "router", "gate", "up", "head")


def _shared_term(config: dict, path, shape):
    """The shared component of one kernel, (fan_out,) float32, or None:
    ``SHARED[name] / fan_in`` on every column of ``wdq`` and on the rope
    columns of ``wuq`` (each head's last ``qk_rope_head_dim``) and of
    ``wdkv`` (past the latent), times ``(1 + layer)^0.5`` for the two
    that read the normed input."""
    import numpy as np

    name = path[-1].key
    if name not in SHARED:
        return None
    fan_in, fan_out = shape[-2:]
    size = SHARED[name] / fan_in
    column = np.arange(fan_out)
    if name == "wuq":
        head = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        on = column % head >= config["qk_nope_head_dim"]
    else:
        size *= math.sqrt(1.0 + path[1].idx)
        on = column >= (config["kv_lora_rank"] if name == "wdkv" else 0)
    return np.where(on, size, 0.0).astype(np.float32)


def seeded_params(config: dict, seed: int, device):
    """The checkpoint, made on the device in one jitted call from the
    seed: the hashed counter of ``perfbench/weights.py`` under this
    kind's own scales (a stacked expert kernel's fan-in is its second to
    last axis)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.deepseek import param_shapes

    from perfbench.weights import _MIX1, _hashed_bits, seed_words

    shapes = param_shapes(deepseek_config(config))
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(words):
        base = _hashed_bits(2, words[0])[1] ^ words[1]
        leaves = []
        for i, (path, spec) in enumerate(paths_leaves):
            name = path[-1].key
            if name.endswith("norm"):
                leaves.append(jnp.ones(spec.shape, spec.dtype))
                continue
            mean, std = LEAVES.get(name) or (
                0.0, GAINS.get(name, 1.0) * spec.shape[-2] ** -0.5)
            bits = _hashed_bits(math.prod(spec.shape),
                                base + jnp.uint32((i * _MIX1) & 0xFFFFFFFF))
            unit = ((bits >> 16).astype(jnp.float32) + 0.5) / 65536.0 - 0.5
            leaf = (mean + unit * (std * math.sqrt(12.0))).reshape(spec.shape)
            if name in BLIND:
                leaf = leaf - leaf.mean(-2, keepdims=True)
            shared = _shared_term(config, path, spec.shape)
            if shared is not None:
                leaf = leaf + shared
            leaves.append(leaf.astype(spec.dtype))
        return leaves

    jitted = jax.jit(fill) if device is None else jax.jit(
        fill, out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.tree_util.tree_unflatten(treedef, jitted(seed_words(seed)))


def build(config: dict, seed: int, device):
    """A ``ModelRegistry`` whose text loader hands out the benchmark's
    seeded weights; the pipeline, its two programs and the residency
    ledger are the program's own. The ledger's budget is the
    configuration's ``serving.residency_budget_fraction`` of the chip
    (the operator's knob; the default would degrade a model of this size
    to load-per-job)."""
    from chiaswarm_tpu.core.mesh import device_hbm_bytes
    from chiaswarm_tpu.models.tokenizer import WordPieceTokenizer
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.text import TextComponents, word_vocab
    from chiaswarm_tpu.serving.residency import ResidencyManager

    serving = config["serving"]
    params = seeded_params(config, seed, device)
    components = TextComponents(
        config=deepseek_config(config), model_name=f"bench/{config['name']}",
        tokenizer=WordPieceTokenizer(word_vocab(config["vocab_size"])),
        params=params)

    class SeededRegistry(ModelRegistry):
        def _load_text_components(self, model_name):
            return components

    budget = int(serving["residency_budget_fraction"]
                 * device_hbm_bytes(device))
    registry = SeededRegistry(
        catalog=[{"name": components.model_name,
                  "stack": components.config.stack,
                  "prefill_chunk": serving["prefill_chunk"],
                  "max_context": serving["max_context"]}],
        residency=ResidencyManager(budget_bytes=budget))
    return registry, params, components.model_name


# ---- comparison ----------------------------------------------------------


def reference_logprobs(params, config: dict, job: dict, rows,
                       precision: str = "float32"):
    """The reference's log-probability of every token of ``rows``
    (n, N) after the job's prompt."""
    from perfbench import deepseekref

    logits = deepseekref.forward_tree(
        params, deepseekref.sizes(config), ids_of(job["prompt"], config),
        rows, precision)
    return deepseekref.token_logprobs(logits, rows)


def check(params, config: dict, good: list[dict], sent: dict, *,
          seed: int, n_jobs: int | None, decode=decode_artifact) -> dict:
    import numpy as np

    spec = config["compare"]
    n_jobs = int(spec["jobs"] if n_jobs is None else n_jobs)
    limit = float(spec["logprob_gap_limit"])
    median_limit = float(spec["logprob_gap_median_limit"])
    rows, gaps = [], []
    for item in compare.pick(good, sent, seed, n_jobs, job_size):
        job = sent[item["id"]]["job"]
        served = served_rows(decode(item["result"]), config, job)
        gap = np.full((1,), np.inf)
        if served is not None:
            want = reference_logprobs(params, config, job, served[0])
            gap = np.abs(served[1] - want).ravel()
        gaps.append(gap)
        rows.append({"id": item["id"], "tokens": job_size(job),
                     "gap": float(gap.max()),
                     "gap_median": float(np.median(gap))})
    gaps = np.concatenate(gaps) if gaps else np.full((1,), np.inf)
    worst, median = float(gaps.max()), float(np.median(gaps))
    return {"ok": worst <= limit and median <= median_limit, "jobs": rows,
            "numbers": {
                "logprob_gap": {"value": worst, "limit": limit},
                "logprob_gap_median": {"value": median,
                                       "limit": median_limit}}}


def control(params, config: dict, jobs: list[dict], *, seed: int) -> dict:
    """``check`` over ``jobs`` as if the lower-precision reference had
    served them: two rows of ids drawn from the job's seed, with the
    log-probabilities that reference gives them."""
    import numpy as np

    precision = compare.CONTROL_OF[config["serving"]["dtype"]]
    good, sent = [], {}
    for order, job in enumerate(jobs):
        rng = random.Random(f"{int(seed)}:control:{job['id']}")
        rows = np.asarray([rng.choices(range(config["vocab_size"]),
                                       k=job["max_new_tokens"])
                           for _ in ROWS])
        logprobs = reference_logprobs(params, config, job, rows, precision)
        sequences = [None] * job["num_return_sequences"]
        for at, row, served in zip(ROWS, rows, logprobs):
            sequences[at] = {
                "text": " ".join(word(int(i), config) for i in row),
                "token_logprobs": [float(x) for x in served]}
        good.append({"id": job["id"], "t": float(order),
                     "result": {"sequences": sequences}})
        sent[job["id"]] = {"job": job}
    verdict = check(params, config, good, sent, seed=seed,
                    n_jobs=len(jobs), decode=lambda payload: payload)
    verdict["precision"] = precision
    return verdict


# ---- the work of a job ---------------------------------------------------


def _weights(config: dict) -> dict:
    """Parameter counts by where a step reads them."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, ropew, dv = (config["qk_nope_head_dim"],
                       config["qk_rope_head_dim"], config["v_head_dim"])
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    n = config["num_hidden_layers"]
    dense = min(n, config["first_k_dense_replace"])
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        "mla": d * q_rank + q_rank * h * (nope + ropew) + d * (rank + ropew)
        + rank * h * (nope + dv) + h * dv * d,
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": expert,
        "shared": config["n_shared_experts"] * expert,
        "router": d * config["published"]["n_routed_experts"],
        "head": d * config["vocab_size"],
        "layers": (n, dense, n - dense)}


def _sizes(config: dict, job: dict):
    """(prompt tokens, rows, new tokens, decode steps x rows)."""
    p = len(job["prompt"].split())
    rows, new = job["num_return_sequences"], job["max_new_tokens"]
    return p, rows, new, rows * (new - 1)


def _token_flops(config: dict) -> float:
    """Operations one token needs outside attention's scores and the
    head: every projection, the dense MLP or the router, the shared
    experts and its share of the routed ones (6 chosen x the share
    held); multiply-adds as two."""
    w = _weights(config)
    n, dense, moe = w["layers"]
    held_share = config["n_routed_experts"] \
        / config["published"]["n_routed_experts"]
    return 2.0 * (
        n * w["mla"] + dense * w["dense_mlp"]
        + moe * (w["router"] + w["shared"] + w["expert"]
                 * config["num_experts_per_tok"] * held_share))


def job_flops(config: dict, job: dict) -> float:
    """Operations the job needs, multiply-adds as two: every prompt token
    through ``_token_flops`` and latent attention in the up-projected
    form over the causal half, the head once for the first new token,
    and the decode (``decode_flops`` at the pairs the job's sizes give)."""
    w = _weights(config)
    h = config["num_attention_heads"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    p = _sizes(config, job)[0]
    prefill_attn = w["layers"][0] * 2.0 * h * width * p * (p + 1) / 2
    return _token_flops(config) * p + prefill_attn + 2.0 * w["head"] \
        + decode_flops(config, job, decode_pairs(config, job))


def decode_pairs(config: dict, job: dict) -> int:
    """Query-key pairs a head scores in the job's decode, summed over
    layers, steps and rows: what the program's counter
    ``chiaswarm_text_attention_pairs_total{decode}`` adds for the job
    when its rows and new tokens fill their buckets."""
    p, rows, new, _ = _sizes(config, job)
    steps = new - 1
    return config["num_hidden_layers"] * rows * (
        steps * (p + 1) + steps * (steps - 1) // 2)


def decode_flops(config: dict, job: dict, attention_pairs: float) -> float:
    """Operations the decode of one job needs, whatever implements it,
    multiply-adds as two: every weight outside the routed experts and
    the held share of the routed ones a row a step, the head, and the
    absorbed scores and read-out over ``attention_pairs`` query-key
    pairs a head (the program's count): latent + rope wide going in, the
    latent wide coming out. The absorption (the query into the latent
    space, the read-out out of it: 2 x heads x rank x (nope + v) a token
    a layer) is what ``_token_flops`` counts as W_ukv's product, which
    the absorbed form does not apply to a decoded token."""
    h = config["num_attention_heads"]
    rank, ropew = config["kv_lora_rank"], config["qk_rope_head_dim"]
    decoded = _sizes(config, job)[3]
    return decoded * (_token_flops(config)
                      + 2.0 * _weights(config)["head"]) \
        + 2.0 * h * (rank + ropew + rank) * attention_pairs


def decode_bytes(config: dict, job: dict, experts_hit: float) -> float:
    """Bytes the decode of one job has to move between memory and the
    chip's cores, whatever implements it: at each of its ``new - 1``
    steps every weight outside the routed experts once (the head and the
    routers included), the prompt's latents once a layer and each row's
    own suffix; plus the weights of the held experts that were hit
    (``experts_hit``: the program's count, summed over the job's steps
    and layers)."""
    w = _weights(config)
    n, dense, moe = w["layers"]
    item = 2                                            # bfloat16
    p, rows, new, _ = _sizes(config, job)
    latent = (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * item
    fixed = item * (n * w["mla"] + dense * w["dense_mlp"]
                    + moe * w["shared"] + w["head"]) + 4 * moe * w["router"]
    latents = n * (p + rows * new / 2.0) * latent
    return (new - 1) * (fixed + latents) + experts_hit * item * w["expert"]


def prefill_chunks(config: dict, job: dict) -> int:
    """Executions of the prefill program one job makes."""
    return -(-_sizes(config, job)[0] // config["serving"]["prefill_chunk"])


def prefill_attention_flops(config: dict, job: dict,
                            attention_pairs: float) -> float:
    """Operations the attention of one job's prompt needs, multiply-adds
    as two: over ``attention_pairs`` query-key pairs a head (the
    program's count over the causal half: layers x p (p + 1) / 2), nope
    + rope wide going in and the value wide coming out."""
    return 2.0 * config["num_attention_heads"] * attention_pairs * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def prefill_attention_bytes(config: dict, job: dict) -> float:
    """Bytes that attention has to move a job, whatever implements it:
    a layer a chunk, every head's query and read-out of the chunk's
    tokens once, the up-projected keys and values and the shared rotary
    key of every token up to the chunk's end once."""
    h, item = config["num_attention_heads"], 2          # bfloat16
    nope, ropew, dv = (config["qk_nope_head_dim"],
                       config["qk_rope_head_dim"], config["v_head_dim"])
    p, chunk = _sizes(config, job)[0], config["serving"]["prefill_chunk"]
    keys = sum(min(p, (i + 1) * chunk)
               for i in range(prefill_chunks(config, job)))
    return config["num_hidden_layers"] * item * (
        p * h * (nope + ropew + dv) + keys * (h * (nope + dv) + ropew))


def kernel_sites(config: dict) -> list[tuple]:
    """None: the one Mosaic kernel of these programs is the causal
    prefill kernel (``ops/causal_flash_attention.py``), whose work
    depends on a traced offset (the chunk's position decides how many
    key blocks it reads), so no one (name, shape) site prices it; the
    cell is not listed under ``flash_roofline.lat`` (ROADMAP R3d).
    ``causal_flash_attention_roofline.lat`` prices it over a whole
    prefill instead (``prefill_attention_flops`` / ``_bytes`` above)."""
    return []


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    from perfbench import deepseekref

    for key in deepseekref.KEYS + ("vocab_size", "n_routed_experts",
                                   "n_shared_experts", "experts_held",
                                   "intermediate_size",
                                   "moe_intermediate_size", "rope_scaling",
                                   "left_out"):
        assert key in config, key
    assert set(deepseekref.YARN_KEYS) <= set(config["rope_scaling"])
    assert config["rope_scaling"]["type"] == "yarn"
    assert config["scoring_func"] == "softmax"
    assert config["topk_method"] == "group_limited_greedy"
    assert config["norm_topk_prob"] is False
    first, past = config["experts_held"]
    # the chip's share: as many experts as the file counts, whole router
    # groups, and the router itself never cut
    assert past - first == config["n_routed_experts"]
    routed = config.get("published", {}).get("n_routed_experts",
                                             config["n_routed_experts"])
    assert 0 <= first < past <= routed
    assert (past - first) % (routed // config["n_group"]) == 0
    serving = config["serving"]
    assert set(serving) == {"workflow", "dtype", "state_dtype",
                            "router_dtype", "prefill_chunk", "max_context",
                            "content_type", "residency_budget_fraction",
                            "temperature", "logprobs"}
    # the comparison reads the served log-probabilities
    assert serving["logprobs"] is True and serving["temperature"] > 0
    assert serving["workflow"] == "txt2txt"
    assert serving["max_context"] % serving["prefill_chunk"] == 0
    assert 0 < serving["residency_budget_fraction"] < 0.9
    assert config["compare"]["logprob_gap_limit"] \
        > config["compare"]["logprob_gap_median_limit"] > 0
