"""The text-generation kind for a Laguna-XS.2-class stack: the ``textgen``
kind's txt2txt jobs (``perfbench/kinds/textgen.py``: a prompt prefilled
in chunks, every row sampled in one scan, a JSON text artifact back)
served by ``chiaswarm_tpu/models/laguna.py``. Owns
``perfbench/lagunaref.py`` (the plain float32 decoder).

From ``textgen`` comes what does not know the model: the unit of work
``[prompt tokens, new tokens, rows]``, the vocabulary of base-26 words,
the job, the artifact, the served rows and the mix's rules. This module
holds what does: the configuration in the program's terms, the seeded
weights and their scales, the registry, the reference, the work of a job
in operations and bytes by layer type. ``check`` and ``control`` are
``textgen``'s restated, because those call their own module's reference
(folding the three is a ``benchmark`` PR's).

What decides ``correct``, as in the other text cells: the served
``token_logprobs`` of the first and the last row of a sample of the
window's jobs against the reference run teacher-forced over the prompt
and the served tokens:

    logprob_gap        = max    |served token_logprob - reference's|
    logprob_gap_median = median |served token_logprob - reference's|

The widest gap is set by the router (the 8th and 9th best of 256
probabilities lie closer than a bfloat16 hidden state moves them, so
program and reference now and then choose another expert, in ANY
precision below float32) and by a softmax that is peaked on purpose; the
median leaves that tail out and tells the precisions apart. Every layer
of this stack is softmax attention over plain keys and values, so the
comparison has to see those layers (with fan-in weights over 16,384
near-equal scores a layer adds next to nothing, and no fault in it moves
either number): ``GAINS`` below; and it has to see the state the decode
keeps, each row's suffix of keys and values in both kinds of layer:
``SHARED`` and ``BLIND`` below. The limits are in the configuration's
file with the readings they were set from; the planted faults are in
PERF.md (PR 35).
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

FULL, SLIDING = "full_attention", "sliding_attention"

# ---- the configuration's sizes, in the program's terms -------------------


def laguna_config(config: dict):
    """The program's ``LagunaConfig`` of the configuration's file: every
    width as published, the per-layer lists as cut, the experts as
    held."""
    import dataclasses

    from chiaswarm_tpu.models.laguna import LagunaConfig, Rope, RopeParameters

    lists = ("layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer")
    names = {f.name for f in dataclasses.fields(LagunaConfig)}
    sizes = {k: v for k, v in config.items()
             if k in names and k not in lists + ("experts_held",
                                                 "rope_parameters")}
    rope_names = {f.name for f in dataclasses.fields(Rope)}
    ropes = {kind: Rope(**{k: v for k, v in
                           config["rope_parameters"][kind].items()
                           if k in rope_names})
             for kind in (FULL, SLIDING)}
    return LagunaConfig(
        experts_held=tuple(config["experts_held"]),
        rope_parameters=RopeParameters(**ropes),
        dtype=config["serving"]["dtype"],
        **{name: tuple(config[name]) for name in lists}, **sizes)


# ---- weights and registry ------------------------------------------------

#: leaf name -> (mean, standard deviation); a kernel not listed is
#: fan-in scaled, a name ending in ``norm`` is ones. The embedding's
#: mean is the shared component of ``SHARED`` below.
LEAVES = {"embed": (0.2, 1.0)}

#: leaf name -> factor on its fan-in scale. Fan-in weights give a head
#: scores of standard deviation 1 in a sliding layer (128 unit products
#: times 128^-0.5) and 1.6 in a full one (the rotated half carries
#: ``attention_factor`` squared), a softmax of ~190 of 512 and ~1,300 of
#: 16,384 effective keys, whose read-out is a few hundredths of the
#: residual: layers the comparison cannot see. ``wq`` x 1.6 puts the
#: scores at 1.6 and 2.5: ~40 effective keys in a window, ~30 in a full
#: layer, as peaked as a trained head and still many keys. It scales the
#: rotated and the unrotated part of the query alike, so a wrong
#: position and a wrong rotated width both show. ``wo`` x 4 makes the
#: seven attention blocks (gated: each head's read-out halves) add about
#: what the MLPs add to the residual, so that a fault in one layer's
#: read-out moves the logits. The router stays fan-in scaled: unit
#: logits, the top of 256 probabilities ~0.04, and 32 rows x 8 choices
#: hit about 162 of the 256 experts a layer a step
#: (``moe_experts_hit.lat``).
GAINS = {"wq": 1.6, "wo": 4.0}

#: layer type -> size of a shared component: ``size / fan_in`` added to
#: every weight of the ROTATED columns of every head of ``wq`` and of
#: ``wk`` (a full layer's first 64 of 128, a sliding layer's all), so
#: that both kernels carry the mean of their input into each of them.
#: Independent weights know no recency: a decoded token's own suffix is
#: at most 128 of 16,512 keys of a full layer and gets under 1% of a
#: head's softmax, so a suffix cache that is never written would move
#: neither number (PERF.md, PR 33). Every token's embedding has the same
#: small mean (``LEAVES``: 0.2 beside a unit deviation, 0.196 of the
#: normed input), q and k then hold one and the same vector ``c`` before
#: the rotation, and their product after it is ``2 c_q c_k sum_i cos((m
#: - n) f_i)`` over the layer type's frequencies: largest at distance 0,
#: falling over a few tokens as the fast pairs turn, level where only
#: the slow pairs are left (YaRN's divided pairs stay aligned over the
#: whole context: half of the peak in a full layer; a third of it at the
#: far end of a window). Sizes: a reckoning of one head's softmax (content
#: scores of deviation 2.5 / 1.6 plus that profile) gave the range, three
#: sittings on the chip the values (PERF.md, PR 35: sound median /
#: weakest planted fault at (full, sliding) = (7.0, 3.5) 0.168 / 0.233,
#: (8.0, 3.5) 0.150 / 0.195, (9.0, 2.5) 0.123 / 0.189). 9.0 puts the bias
#: of a full layer at ~35 at distance 0 against ~18 far away, and a head's
#: softmax mostly on the last few dozen tokens of 16,448 (the prompt's
#: keys by content beside them): a stronger recency also makes the model
#: less noisy under bfloat16, which is why the sound median FELL as it
#: rose. 2.5 puts a sliding layer's at ~2.7 against ~0.9: a fifth of the
#: softmax on the last 64 of 512, so that what the window holds (and
#: what lies outside it) still decides the read-out. The residual's
#: variance grows by up to one a layer while the shared mean stays, so a
#: normed input's mean falls as ``(1 + layer)^-0.5``; both kernels grow
#: their term by ``(1 + layer)^0.5`` to hold the product level over the
#: seven layers.
SHARED = {FULL: 9.0, SLIDING: 2.5}

#: kernels that read the normed residual: each column sums to zero over
#: its fan-in, so the residual's shared mean moves nothing through them
#: and only the terms of ``SHARED`` read it. Left to leak, it reaches
#: every row's router alike, the rows lean to the same experts, and the
#: experts hit a layer a step fall with the seed (PERF.md, PR 33:
#: ``job_p50_s`` then spreads by 2.7%).
BLIND = ("wq", "wk", "wv", "wg", "router", "gate", "up", "head")


def _shared_term(config: dict, path, shape):
    """The shared component of one kernel, (fan_out,) float32, or None:
    ``SHARED[layer type] / fan_in`` on the rotated columns of every head
    of ``wq`` and ``wk``, times ``(1 + layer)^0.5``."""
    import numpy as np

    if path[-1].key not in ("wq", "wk"):
        return None
    layer = path[1].idx
    fan_in, fan_out = shape[-2:]
    kind = config["layer_types"][layer]
    rotated = int(config["head_dim"] * config["rope_parameters"][kind][
        "partial_rotary_factor"])
    size = SHARED[kind] / fan_in * (1.0 + layer) ** 0.5
    on = np.arange(fan_out) % config["head_dim"] < rotated
    return np.where(on, size, 0.0).astype(np.float32)


def seeded_params(config: dict, seed: int, device):
    """The checkpoint, made on the device in one jitted call from the
    seed: the hashed counter of ``perfbench/weights.py`` under this
    kind's own scales (a stacked expert kernel's fan-in is its second to
    last axis)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.laguna import param_shapes

    from perfbench.weights import _MIX1, _hashed_bits, seed_words

    shapes = param_shapes(laguna_config(config))
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
        config=laguna_config(config), model_name=f"bench/{config['name']}",
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
    from perfbench import lagunaref

    logits = lagunaref.forward_tree(
        params, lagunaref.sizes(config), ids_of(job["prompt"], config),
        rows, precision)
    return lagunaref.token_logprobs(logits, rows)


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


# ---- the work of a job, by layer type ------------------------------------

ITEM = 2                                                # bfloat16


def _layers(config: dict) -> dict:
    """Layer indices by attention type, and by MLP type."""
    types, mlps = config["layer_types"], config["mlp_layer_types"]
    return {FULL: [i for i, t in enumerate(types) if t == FULL],
            SLIDING: [i for i, t in enumerate(types) if t == SLIDING],
            "dense": [i for i, t in enumerate(mlps) if t == "dense"],
            "sparse": [i for i, t in enumerate(mlps) if t == "sparse"]}


def _heads(config: dict, kind: str) -> int:
    """Query heads summed over the layers of one attention type."""
    return sum(config["num_attention_heads_per_layer"][i]
               for i in _layers(config)[kind])


def _weights(config: dict) -> dict:
    """Parameter counts by where a step reads them."""
    d, dh = config["hidden_size"], config["head_dim"]
    kv = 2 * d * config["num_key_value_heads"] * dh
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        # W_q, W_g and W_o a query head; W_k and W_v a layer
        "attention": sum(h * (2 * d * dh + d) + kv
                         for h in config["num_attention_heads_per_layer"]),
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": expert,
        "shared": 3 * d * config["shared_expert_intermediate_size"],
        "router": d * config["num_experts"],
        "head": d * config["vocab_size"]}


def _sizes(config: dict, job: dict):
    """(prompt tokens, rows, new tokens, decode steps x rows)."""
    p = len(job["prompt"].split())
    rows, new = job["num_return_sequences"], job["max_new_tokens"]
    return p, rows, new, rows * (new - 1)


def _seen(first: int, count: int, window: int) -> int:
    """Keys inside a window summed over the queries at positions
    [first, first + count): a query at p sees min(p + 1, window)."""
    return sum(min(p + 1, window) for p in range(first, first + count))


def window_pairs(config: dict, job: dict) -> tuple[int, int]:
    """Query-key pairs a head scores inside the window, summed over the
    sliding layers: (in the prompt's prefill, in the decode of the job's
    rows): the sliding layers' part of what the program's counters
    ``chiaswarm_text_attention_pairs_total{phase}`` count, and, both
    together, ``chiaswarm_text_window_pairs_total{visible}``."""
    p, rows, new, _ = _sizes(config, job)
    n, window = len(_layers(config)[SLIDING]), config["sliding_window"]
    return n * _seen(0, p, window), n * rows * _seen(p, new - 1, window)


def _token_flops(config: dict) -> float:
    """Operations one token needs outside attention's scores and the
    head: every projection, the dense MLP or the router, the shared
    expert and its share of the routed ones (8 chosen x the share held);
    multiply-adds as two."""
    w, layers = _weights(config), _layers(config)
    first, past = config["experts_held"]
    held_share = (past - first) / config["num_experts"]
    return 2.0 * (
        w["attention"] + len(layers["dense"]) * w["dense_mlp"]
        + len(layers["sparse"]) * (
            w["router"] + w["shared"]
            + w["expert"] * config["num_experts_per_tok"] * held_share))


def _score_flops(config: dict, pairs: float, window_pairs: float) -> float:
    """Operations of the scores and read-outs over ``pairs`` query-key
    pairs a head summed over ALL layers, of which ``window_pairs`` are
    the sliding layers': each pair ``head_dim`` going in and coming out,
    at the layer type's head count (the full layers share one, the
    sliding layers another)."""
    layers = _layers(config)
    full = (pairs - window_pairs) / max(len(layers[FULL]), 1)
    sliding = window_pairs / max(len(layers[SLIDING]), 1)
    return 4.0 * config["head_dim"] * (
        full * _heads(config, FULL) + sliding * _heads(config, SLIDING))


def job_flops(config: dict, job: dict) -> float:
    """Operations the job needs, multiply-adds as two: every prompt token
    through ``_token_flops`` and attention over the pairs a head sees
    (the causal half in a full layer, the window in a sliding one), the
    head once for the first new token, and the decode (``decode_flops``
    at the pairs the job's sizes give)."""
    p = _sizes(config, job)[0]
    full = len(_layers(config)[FULL]) * p * (p + 1) // 2
    seen = window_pairs(config, job)[0]
    return _token_flops(config) * p \
        + _score_flops(config, full + seen, seen) \
        + 2.0 * _weights(config)["head"] \
        + decode_flops(config, job, decode_pairs(config, job))


def decode_pairs(config: dict, job: dict) -> int:
    """Query-key pairs a head scores in the job's decode, summed over
    layers, steps and rows: what the program's counter
    ``chiaswarm_text_attention_pairs_total{decode}`` adds for the job
    when its rows and new tokens fill their buckets."""
    p, rows, new, _ = _sizes(config, job)
    steps = new - 1
    full = len(_layers(config)[FULL]) * rows * (
        steps * (p + 1) + steps * (steps - 1) // 2)
    return full + window_pairs(config, job)[1]


def decode_flops(config: dict, job: dict, attention_pairs: float) -> float:
    """Operations the decode of one job needs, whatever implements it,
    multiply-adds as two: every weight outside the routed experts and
    the held share of the routed ones a row a step, the head, and the
    scores and read-outs over ``attention_pairs`` query-key pairs a head
    (the program's count over all layers; the sliding layers' part of it
    is ``window_pairs``' and priced at their head count)."""
    decoded = _sizes(config, job)[3]
    return decoded * (_token_flops(config)
                      + 2.0 * _weights(config)["head"]) \
        + _score_flops(config, attention_pairs,
                       window_pairs(config, job)[1])


def decode_bytes(config: dict, job: dict, experts_hit: float) -> float:
    """Bytes the decode of one job has to move between memory and the
    chip's cores, whatever implements it: at each of its ``new - 1``
    steps every weight outside the routed experts once (the head and the
    routers included), a full layer's prompt keys and values once and a
    sliding layer's last window of them, each row's own suffix (a
    sliding layer's as far as the window reaches); plus the weights of
    the held experts that were hit (``experts_hit``: the program's
    count, summed over the job's steps and layers)."""
    w, layers = _weights(config), _layers(config)
    p, rows, new, _ = _sizes(config, job)
    window = config["sliding_window"]
    entry = 2 * config["num_key_value_heads"] * config["head_dim"] * ITEM
    fixed = ITEM * (w["attention"] + len(layers["dense"]) * w["dense_mlp"]
                    + len(layers["sparse"]) * w["shared"] + w["head"]) \
        + 4 * len(layers["sparse"]) * w["router"]
    caches = entry * (
        len(layers[FULL]) * (p + rows * new / 2.0)
        + len(layers[SLIDING]) * (min(p, window)
                                  + rows * min(new / 2.0, window)))
    return (new - 1) * (fixed + caches) + experts_hit * ITEM * w["expert"]


def prefill_chunks(config: dict, job: dict) -> int:
    """Executions of the prefill program one job makes."""
    return -(-_sizes(config, job)[0] // config["serving"]["prefill_chunk"])


def _chunk_keys(config: dict, job: dict, window: int | None) -> int:
    """Keys a layer's prefill has to read, summed over a job's chunks:
    every token up to a chunk's end, or the window before it and the
    chunk."""
    p, chunk = _sizes(config, job)[0], config["serving"]["prefill_chunk"]
    ends = [min(p, (i + 1) * chunk)
            for i in range(prefill_chunks(config, job))]
    if window is None:
        return sum(ends)
    return sum(min(end, window + end - i * chunk)
               for i, end in enumerate(ends))


def _attention_bytes(config: dict, job: dict, kind: str) -> float:
    """Bytes the prefill attention of one layer type has to move a job:
    every head's query and read-out of the prompt's tokens once, the
    keys and values a chunk sees once a chunk."""
    p = _sizes(config, job)[0]
    dh, hk = config["head_dim"], config["num_key_value_heads"]
    window = config["sliding_window"] if kind == SLIDING else None
    return ITEM * dh * (
        2 * p * _heads(config, kind)
        + len(_layers(config)[kind]) * 2 * hk
        * _chunk_keys(config, job, window))


def prefill_attention_flops(config: dict, job: dict,
                            attention_pairs: float) -> float:
    """Operations the FULL layers' attention of one job's prompt needs
    (the operations named ``causal_flash_attention``), multiply-adds as
    two: ``attention_pairs`` is the program's count over ALL layers
    (``chiaswarm_text_attention_pairs_total{prefill}``); the sliding
    layers' visible pairs (``window_pairs``) go to
    ``window_attention_flops``, the rest are the full layers' causal
    halves, each pair ``head_dim`` going in and coming out at their head
    count."""
    seen = window_pairs(config, job)[0]
    return _score_flops(config, attention_pairs, seen) \
        - _score_flops(config, seen, seen)


def prefill_attention_bytes(config: dict, job: dict) -> float:
    return _attention_bytes(config, job, FULL)


def window_attention_flops(config: dict, job: dict) -> float:
    """Operations the SLIDING layers' attention of one job's prompt
    needs (the operations named ``window_flash_attention``): at the
    VISIBLE pairs only, whatever the kernel steps beside them."""
    seen = window_pairs(config, job)[0]
    return _score_flops(config, seen, seen)


def window_attention_bytes(config: dict, job: dict) -> float:
    return _attention_bytes(config, job, SLIDING)


def decode_attention_flops(config: dict, job: dict) -> float:
    """Operations the full layers' sweep over the shared prompt needs a
    job (the operations named ``shared_prompt_attention``): every row's
    every head against the prompt's keys and values at each step."""
    p, _, _, decoded = _sizes(config, job)
    return 4.0 * config["head_dim"] * _heads(config, FULL) * decoded * p


def decode_attention_bytes(config: dict, job: dict) -> float:
    """Bytes that sweep has to move a job: the prompt's keys and values
    once a full layer a step for all rows, the rows' queries in and
    their float32 read-outs and log-sum-exps out."""
    p, rows, new, decoded = _sizes(config, job)
    dh, hk = config["head_dim"], config["num_key_value_heads"]
    return (new - 1) * len(_layers(config)[FULL]) * 2 * hk * dh * ITEM * p \
        + decoded * _heads(config, FULL) * (dh * (ITEM + 4) + 4)


def kernel_sites(config: dict) -> list[tuple]:
    """None. The programs call three Mosaic kernels, all entries of
    ``ops/causal_flash_attention.py``: ``causal_flash_attention`` (the
    full layers' prefill, grouped by key-value head),
    ``window_flash_attention`` (the sliding layers' prefill) and
    ``shared_prompt_attention`` (the full layers' decode over the shared
    prompt). The work of each depends on a traced offset (a chunk's
    position, the prompt's length), so no one (name, shape) site prices
    it and the cell is not listed under ``flash_roofline.lat``; each has
    its own metric over a whole job's calls instead
    (``causal_flash_attention_roofline.lat``,
    ``window_flash_attention_roofline.lat``,
    ``shared_prompt_attention_roofline.lat``: the six functions
    above)."""
    return []


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    from perfbench import lagunaref

    for key in lagunaref.KEYS + ("vocab_size", "num_experts", "experts_held",
                                 "intermediate_size",
                                 "moe_intermediate_size",
                                 "shared_expert_intermediate_size",
                                 "rope_parameters", "left_out"):
        assert key in config, key
    n = config["num_hidden_layers"]
    for name in ("layer_types", "mlp_layer_types",
                 "num_attention_heads_per_layer"):
        assert len(config[name]) == n, name
        # a cut in depth keeps the published list's first entries
        published = config.get("published", {}).get(name)
        assert published is None or published[:n] == config[name], name
    assert set(config["layer_types"]) == {FULL, SLIDING}
    assert set(config["mlp_layer_types"]) <= {"dense", "sparse"}
    for heads in config["num_attention_heads_per_layer"]:
        assert heads % config["num_key_value_heads"] == 0
    for kind in (FULL, SLIDING):
        group = config["rope_parameters"][kind]
        assert set(lagunaref.ROPE_KEYS) <= set(group)
        if group["rope_type"] == "yarn":
            assert set(lagunaref.YARN_KEYS) <= set(group)
    assert config["gating"] is True
    assert config["moe_apply_router_weight_on_input"] is False
    first, past = config["experts_held"]
    # every expert is held here: the key that counts them is not cut
    assert (first, past) == (0, config["num_experts"])
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
