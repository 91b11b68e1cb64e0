"""The text-generation kind: txt2txt jobs through the worker's per-job
path (a prompt prefilled in chunks, then every row sampled in one scan),
a JSON text artifact back. Owns ``perfbench/textref.py`` (the plain
float32 decoder).

The unit of work (``UNIT``) is ``[prompt tokens, new tokens, rows]``
(rows = ``num_return_sequences``); ``temperature`` and ``logprobs`` are
the configuration's (``serving``), the same for every job: the generator
hands a kind the unit and the configuration, not the mix. A job's prompt
is that many ids drawn from the vocabulary held here by the job's RNG,
written as words (id ``i`` = its base-26 digits in letters), so every
served id reads back from the artifact.

What decides ``correct``: the served ``token_logprobs`` against the
plain reference. After the window has closed and the worker is gone, a
sample of the window's jobs (``compare.pick``) is recomputed: for the
first and the last row of each, the reference runs teacher-forced over
the prompt and the served tokens (``textref.forward_tree``: the prompt
once, each row from the state after it), layer by layer in float32, and
gives the log-probability of every served token. Two numbers a run,
over the sampled jobs, their two rows and every token:

    logprob_gap        = max    |served token_logprob - reference's|
    logprob_gap_median = median |served token_logprob - reference's|

The widest gap is set by the router, not by the products' rounding: the
8th and 9th best of 256 candidate scores lie closer than a bfloat16
hidden state moves them, so here and there the program and the float32
reference choose another 8th expert, and that token's log-probability
moves by tenths of a nat in ANY precision below float32. So the widest
gap cannot tell the precisions apart; its limit lies between the
largest a sound run read and what a wrong token fed back reads (PERF.md,
PR 29: faults planted in the decode program at the cell's size). The
median leaves that tail out and is what tells the precisions apart (fp8
reads four times bfloat16): it is the limit the control has to fail,
and a fault that the recurrent state carries on (a wrong token once, a
state not written) moves it too. Neither number sees a fault in the
latent-attention layer's decode while the weights are seeded: over
16,384 near-equal scores that layer adds next to nothing to the
residual (planted: a position off by one, a suffix latent not written).
They cover the tokenizer, the chunked prefill through the recurrent
cache, every decode step (recurrence, the experts held) and the head
over the slice; they do not see the sampler's draw (any token's
log-probability is checked, whichever was drawn). The limits are in the
configuration's file (``compare.logprob_gap_limit``,
``compare.logprob_gap_median_limit``), the readings they were set from
in PERF.md. The control puts the reference one precision down in the
program's place: its log-probabilities of rows drawn uniformly from the
slice by the job's seed (not sampled: the reference has no cache to
sample a row through), judged by the same ``check``.
"""

from __future__ import annotations

import base64
import json
import math
import random

from perfbench import compare

UNIT = "tokens"
PROGRAM_MODULES = ("chiaswarm_tpu.pipelines.text",)

#: rows of each sampled job the reference recomputes
ROWS = (0, -1)


# ---- the configuration's sizes, in the program's terms -------------------


def ling_config(config: dict):
    """The program's ``LingConfig`` of the configuration's file: every
    width as published, the experts and the vocabulary as held."""
    import dataclasses

    from chiaswarm_tpu.models.ling import LingConfig

    names = {f.name for f in dataclasses.fields(LingConfig)}
    sizes = {k: v for k, v in config.items()
             if k in names and k not in ("num_experts", "experts_held")}
    return LingConfig(
        num_experts=int(config.get("published", {}).get(
            "num_experts", config["num_experts"])),
        experts_held=tuple(config["experts_held"]),
        dtype=config["serving"]["dtype"], **sizes)


def word(i: int, config: dict) -> str:
    letters = 1
    while 26 ** letters < config["vocab_size"]:
        letters += 1
    return "".join(chr(97 + i // 26 ** k % 26)
                   for k in reversed(range(letters)))


def ids_of(text: str, config: dict) -> list[int] | None:
    """The ids a text of this vocabulary's words stands for; None if it
    holds anything else."""
    ids = []
    for w in text.split():
        i = 0
        for ch in w:
            if not "a" <= ch <= "z":
                return None
            i = i * 26 + ord(ch) - 97
        if i >= config["vocab_size"] or word(i, config) != w:
            return None
        ids.append(i)
    return ids


# ---- weights and registry ------------------------------------------------

#: leaf name -> (mean, standard deviation); a kernel not listed is
#: fan-in scaled, a name ending in ``norm`` is ones. Two of these are set
#: so that the seed arranges the work and does not change its amount
#: (PERF.md, PR 29: with conv taps of 0.5 and a router bias of 0.02 the
#: experts a decode step hit ran from 37 to 42 by seed, and the job's
#: time with them by 3%): conv taps of 0.1 keep the SiLU behind the
#: short conv near its linear range, so q, k and v have near-zero means
#: and a layer's read-out is not one constant vector that makes some
#: experts popular for every token; a router bias of 0.001 is non-zero
#: and moves no expert's popularity (0.02 moves it by 40%: the 8th and
#: 9th of 256 scores lie 0.007 apart), as a trained bias balances it.
LEAVES = {"embed": (0.0, 1.0), "dt_bias": (-5.0, 1.5 / math.sqrt(3.0)),
          "a_log": (0.0, 0.02), "router_bias": (0.0, 0.001),
          "conv_q": (0.0, 0.1), "conv_k": (0.0, 0.1), "conv_v": (0.0, 0.1)}


def seeded_params(config: dict, seed: int, device):
    """The checkpoint, made on the device in one jitted call from the
    seed: the hashed counter of ``perfbench/weights.py`` under this
    kind's own scales (a stacked expert kernel's fan-in is its second to
    last axis, which the diffusion layout's rule would get wrong)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.ling import param_shapes

    from perfbench.weights import _MIX1, _hashed_bits, seed_words

    shapes = param_shapes(ling_config(config))
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(words):
        base = _hashed_bits(2, words[0])[1] ^ words[1]
        leaves = []
        for i, (path, spec) in enumerate(paths_leaves):
            name = path[-1].key
            if name.endswith("norm"):
                leaves.append(jnp.ones(spec.shape, spec.dtype))
                continue
            mean, std = LEAVES.get(name) or (0.0, spec.shape[-2] ** -0.5)
            bits = _hashed_bits(math.prod(spec.shape),
                                base + jnp.uint32((i * _MIX1) & 0xFFFFFFFF))
            unit = ((bits >> 16).astype(jnp.float32) + 0.5) / 65536.0 - 0.5
            leaves.append((mean + unit * (std * math.sqrt(12.0))
                           ).astype(spec.dtype).reshape(spec.shape))
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
        config=ling_config(config), model_name=f"bench/{config['name']}",
        tokenizer=WordPieceTokenizer(word_vocab(config["vocab_size"])),
        params=params)

    class SeededRegistry(ModelRegistry):
        def _load_text_components(self, model_name):
            return components

    budget = int(serving["residency_budget_fraction"]
                 * device_hbm_bytes(device))
    registry = SeededRegistry(
        catalog=[{"name": components.model_name,
                  "prefill_chunk": serving["prefill_chunk"],
                  "max_context": serving["max_context"]}],
        residency=ResidencyManager(budget_bytes=budget))
    return registry, params, components.model_name


# ---- jobs ----------------------------------------------------------------


def job(rng, job_id: str, unit, config: dict, model_name: str) -> dict:
    """``unit`` = [prompt tokens, new tokens, rows]; the prompt's ids and
    the sampling seed come from ``rng``."""
    prompt_tokens, new_tokens, rows = (int(n) for n in unit)
    serving = config["serving"]
    ids = rng.choices(range(config["vocab_size"]), k=prompt_tokens)
    return {
        "id": job_id,
        "model_name": model_name,
        "workflow": serving["workflow"],
        "prompt": " ".join(word(i, config) for i in ids),
        "seed": rng.randrange(2 ** 31),
        "max_new_tokens": new_tokens,
        "num_return_sequences": rows,
        "temperature": float(serving["temperature"]),
        "logprobs": bool(serving["logprobs"]),
        "content_type": serving["content_type"],
    }


def job_size(job: dict) -> int:
    return len(job["prompt"].split()) \
        + job["num_return_sequences"] * job["max_new_tokens"]


# ---- comparison ----------------------------------------------------------


def decode_artifact(result: dict) -> dict:
    return json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))


def served_rows(payload: dict, config: dict, job: dict):
    """(token ids, served log-probabilities) of the job's first and last
    row; None if the artifact is not what the job asked for."""
    import numpy as np

    sequences = payload.get("sequences")
    if not isinstance(sequences, list) \
            or len(sequences) != job["num_return_sequences"]:
        return None
    ids, logprobs = [], []
    for seq in (sequences[i] for i in ROWS):
        row = ids_of(seq.get("text", ""), config)
        served = seq.get("token_logprobs")
        if row is None or served is None \
                or not len(row) == len(served) == job["max_new_tokens"]:
            return None
        ids.append(row)
        logprobs.append(served)
    return np.asarray(ids), np.asarray(logprobs, np.float64)


def reference_logprobs(params, config: dict, job: dict, rows,
                       precision: str = "float32"):
    """The reference's log-probability of every token of ``rows``
    (n, N) after the job's prompt."""
    from perfbench import textref

    logits = textref.forward_tree(
        params, textref.sizes(config), ids_of(job["prompt"], config), rows,
        precision)
    return textref.token_logprobs(logits, rows)


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


def _layers(config: dict):
    """(KDA layers, MLA layers, dense-MLP layers, expert layers)."""
    n, period = config["num_hidden_layers"], config["layer_group_size"]
    mla = sum((i + 1) % period == 0 for i in range(n))
    dense = min(n, config["first_k_dense_replace"])
    return n - mla, mla, dense, n - dense


def _weights(config: dict) -> dict:
    """Parameter counts by where a step reads them."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    inner = h * config["head_dim"]
    nope, ropew, dv = (config["qk_nope_head_dim"],
                       config["qk_rope_head_dim"], config["v_head_dim"])
    rank = config["kv_lora_rank"]
    kda, mla, dense, moe = _layers(config)
    expert = 3 * d * config["moe_intermediate_size"]
    router = d * config["published"]["num_experts"]
    return {
        "kda": 6 * d * inner + d * h,               # q k v a g o, b
        "mla": d * h * (nope + ropew) + d * (rank + ropew)
        + rank * h * (nope + dv) + d * h + h * dv * d,
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": expert, "router": router,
        "head": d * config["vocab_size"],
        "layers": (kda, mla, dense, moe)}


def job_flops(config: dict, job: dict) -> float:
    """Operations the job needs, multiply-adds as two: every prompt token
    and every new token of every row through the projections, the
    recurrence, its share of the experts (8 chosen x the share held) and,
    where a token is predicted from it, the head; latent attention in
    the up-projected form over the causal half for the prompt and in the
    absorbed form against prompt + suffix for the new tokens."""
    w = _weights(config)
    kda, mla, dense, moe = w["layers"]
    h, dk = config["num_attention_heads"], config["head_dim"]
    nope, ropew, dv = (config["qk_nope_head_dim"],
                       config["qk_rope_head_dim"], config["v_head_dim"])
    rank = config["kv_lora_rank"]
    p = len(job["prompt"].split())
    rows, new = job["num_return_sequences"], job["max_new_tokens"]
    decoded = rows * (new - 1)
    held_share = config["num_experts"] / config["published"]["num_experts"]
    per_token = (
        kda * (2.0 * w["kda"] + 8.0 * h * dk * dk)
        + mla * 2.0 * w["mla"] + dense * 2.0 * w["dense_mlp"]
        + moe * (2.0 * w["router"] + 2.0 * w["expert"]
                 * (1 + config["num_experts_per_tok"] * held_share)))
    prefill_attn = mla * 2.0 * h * (nope + ropew + dv) * p * (p + 1) / 2
    context = p + new / 2.0
    decode_attn = mla * decoded * (
        2.0 * h * (rank + ropew + rank) * context
        + 2.0 * h * rank * (nope + dv))
    head = 2.0 * w["head"] * (1 + decoded)
    return per_token * (p + decoded) + prefill_attn + decode_attn + head


def decode_bytes(config: dict, job: dict, experts_hit: float) -> float:
    """Bytes the decode of one job has to move between memory and the
    chip's cores, whatever implements it: at each of its ``new - 1``
    steps every weight outside the experts once (the head and the
    routers included), each row's recurrent state and conv tails read
    and written, the prompt's latents once and each row's own suffix;
    plus the weights of the held experts that were hit (``experts_hit``:
    the program's count, summed over the job's steps and layers)."""
    w = _weights(config)
    kda, mla, dense, moe = w["layers"]
    item = 2                                            # bfloat16
    h, dk = config["num_attention_heads"], config["head_dim"]
    rows, new = job["num_return_sequences"], job["max_new_tokens"]
    p = len(job["prompt"].split())
    latent = (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * item
    fixed = item * (kda * w["kda"] + mla * w["mla"]
                    + dense * w["dense_mlp"] + moe * w["expert"]
                    + w["head"]) + 4 * moe * w["router"]
    state = kda * rows * 2 * (
        4 * h * dk * dk
        + item * 3 * (config["short_conv_kernel_size"] - 1) * h * dk)
    latents = mla * (p + rows * new / 2.0) * latent
    return (new - 1) * (fixed + state + latents) \
        + experts_hit * item * w["expert"]


def kernel_sites(config: dict) -> list[tuple]:
    """The text programs call no Mosaic kernel."""
    return []


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    from perfbench import textref

    for key in textref.KEYS + ("vocab_size", "num_experts", "experts_held",
                               "intermediate_size",
                               "moe_intermediate_size", "left_out"):
        assert key in config, key
    first, past = config["experts_held"]
    # the chip's share: as many experts as the file counts, whole router
    # groups, and the router itself never cut
    assert past - first == config["num_experts"]
    routed = config.get("published", {}).get("num_experts",
                                             config["num_experts"])
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


def check_mix(mix: dict) -> None:
    for prompt_tokens, new_tokens, rows in (unit for unit, _ in mix[UNIT]):
        # a first and a last row are compared
        assert prompt_tokens >= 1 and new_tokens >= 2 and rows >= 2
    # each shape of the window is warmed solo: the decode program is
    # compiled per (rows, new tokens) bucket
    assert {tuple(unit) for unit, _ in mix[UNIT]} \
        <= {tuple(unit) for unit, _ in mix["warm_solo"]}
