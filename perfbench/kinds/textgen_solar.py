"""The text-generation kind for a Solar-Open2-class stack: the ``textgen``
kind's txt2txt jobs (``perfbench/kinds/textgen.py``: a prompt prefilled
in chunks, every row sampled in one scan, a JSON text artifact back)
served by ``chiaswarm_tpu/models/solar.py``. Owns
``perfbench/solarref.py`` (the plain float32 decoder).

From ``textgen`` comes everything that does not know the model, imported
and not restated: the unit of work ``[prompt tokens, new tokens, rows]``,
the vocabulary of base-26 words, the job, the artifact, the served rows,
the mix's rules, and ``check`` / ``control`` themselves (``_here``: the
same code, reading this module's ``reference_logprobs``). This module
holds what does know the model: the configuration in the program's
terms, the seeded weights and their scales, the registry, the reference,
the work of a job in operations and bytes by layer kind.

What decides ``correct``, as in the other text cells: the served
``token_logprobs`` of the first and the last row of a sample of the
window's jobs against the reference run teacher-forced over the prompt
and the served tokens (``logprob_gap`` the widest, ``logprob_gap_median``
the median). For the comparison to see what this model adds, the seeded
weights give the delta rule write strengths past 1 and decays from slow
to fast (``LEAVES``, ``GAINS``), and give the softmax layers, which have
no position encoding, a query that finds its own token's key
(``SELF``): with fan-in weights alone a head's softmax over 16,384
near-equal scores adds next to nothing and no fault in those layers (a
row's suffix never written among them) moves either number. The limits
are in the configuration's file with the readings they were set from;
the planted faults are in PERF.md (PR 39).
"""

from __future__ import annotations

import math
import random  # noqa: F401  (read by ``control``)
import types

from perfbench import compare  # noqa: F401  (read by ``check``)
from perfbench.kinds import textgen
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


def solar_config(config: dict):
    """The program's ``SolarConfig`` of the configuration's file: every
    width as published, the experts and the vocabulary as held (the
    router keeps the published expert count)."""
    import dataclasses

    from chiaswarm_tpu.models.solar import LinearAttnConfig, SolarConfig

    names = {f.name for f in dataclasses.fields(SolarConfig)}
    special = ("n_routed_experts", "experts_held", "linear_attn_config",
               "gqa_layers")
    sizes = {k: v for k, v in config.items()
             if k in names and k not in special}
    return SolarConfig(
        n_routed_experts=int(config["published"]["n_routed_experts"]),
        experts_held=tuple(config["experts_held"]),
        linear_attn_config=LinearAttnConfig(**config["linear_attn_config"]),
        gqa_layers=tuple(config["gqa_layers"]),
        dtype=config["serving"]["dtype"], **sizes)


# ---- weights and registry ------------------------------------------------

#: leaf name -> (mean, standard deviation) of a uniform fill; a kernel not
#: listed is fan-in scaled, a name ending in ``norm`` is ones. The decay
#: ``log a = -e^A softplus(z + dt_bias)``, z of unit deviation: dt_bias
#: over [-9, -1] and e^A over [1, 16] (Kimi-Linear's range of A) make a
#: channel's log-decay a token run from -1e-4 (remembered over ~10^4
#: tokens) to -5 and below (gone after one). The conv taps and the
#: router's bias are the Ling kind's (``textgen.LEAVES``): taps of 0.1 keep
#: the SiLU behind the short conv near its linear range, so the read-outs
#: are not one constant vector that makes some experts popular for every
#: token, and a bias of 0.001 moves no expert's popularity; the seed then
#: arranges the work without changing its amount.
LEAVES = {"embed": (0.0, 1.0), "dt_bias": (-5.0, 8.0 / math.sqrt(12.0)),
          "a_log": (math.log(16.0) / 2, math.log(16.0) / math.sqrt(12.0)),
          "router_bias": (0.0, 0.001),
          "conv_q": (0.0, 0.1), "conv_k": (0.0, 0.1), "conv_v": (0.0, 0.1)}

#: leaf name -> factor on its fan-in scale. ``wb`` x 2: W_b x of deviation
#: 2, so that b = 2 sigmoid(W_b x) lies over 1 for half of the (token,
#: head) pairs and over 1.5 for ~29% (a write that overshoots: the state
#: along k flips sign), where b not doubled never passes 1.
GAINS = {"wb": 2.0}

#: a softmax layer's W_q holds, in the columns of every query head, its
#: key-value head's W_k times this: q_j . k_s = SELF |k|^2 for a token's
#: own key (11.3 SELF after the 128^-0.5 scale) against a unit-deviation
#: product for another's. At SELF 1 a query puts about two thirds of its
#: softmax on its own token's key (and on the prompt's tokens of the same
#: id) among 16,384: the read-out is mostly the token's own value, a
#: decoded token's key lives only in its row's suffix, and a key-value
#: head read by the wrong query heads loses the match. No mean is shared
#: by the tokens, so no other kernel needs to be blind to one.
SELF = 1.0


def _with_self_match(config: dict, leaves: dict) -> None:
    """Adds ``SELF`` x the key-value head's W_k to every query head's
    columns of W_q, in each softmax layer (``leaves``: path -> leaf)."""
    import jax.numpy as jnp

    d, dh = config["hidden_size"], config["head_dim"]
    hk = config["num_key_value_heads"]
    g = config["num_attention_heads"] // hk
    for layer in range(config["num_hidden_layers"]):
        if layer not in config["gqa_layers"]:
            continue
        wq, wk = (("layers", layer, "attn", name) for name in ("wq", "wk"))
        own = jnp.repeat(leaves[wk].reshape(d, hk, 1, dh), g, axis=2)
        leaves[wq] = (leaves[wq].astype(jnp.float32)
                      + SELF * own.reshape(d, -1).astype(jnp.float32)
                      ).astype(leaves[wq].dtype)


def _key(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def seeded_params(config: dict, seed: int, device):
    """The checkpoint, made on the device in one jitted call from the
    seed: the hashed counter of ``perfbench/weights.py`` under this
    kind's own scales (a stacked expert kernel's fan-in is its second to
    last axis), then the softmax layers' ``SELF`` component."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.solar import param_shapes

    from perfbench.weights import _MIX1, _hashed_bits, seed_words

    shapes = param_shapes(solar_config(config))
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(words):
        base = _hashed_bits(2, words[0])[1] ^ words[1]
        leaves = {}
        for i, (path, spec) in enumerate(paths_leaves):
            name = path[-1].key
            if name.endswith("norm"):
                leaves[_key(path)] = jnp.ones(spec.shape, spec.dtype)
                continue
            mean, std = LEAVES.get(name) or (
                0.0, GAINS.get(name, 1.0) * spec.shape[-2] ** -0.5)
            bits = _hashed_bits(math.prod(spec.shape),
                                base + jnp.uint32((i * _MIX1) & 0xFFFFFFFF))
            unit = ((bits >> 16).astype(jnp.float32) + 0.5) / 65536.0 - 0.5
            leaves[_key(path)] = (mean + unit * (std * math.sqrt(12.0))
                                  ).astype(spec.dtype).reshape(spec.shape)
        _with_self_match(config, leaves)
        return [leaves[_key(path)] for path, _ in paths_leaves]

    jitted = jax.jit(fill) if device is None else jax.jit(
        fill, out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.tree_util.tree_unflatten(treedef, jitted(seed_words(seed)))


def build(config: dict, seed: int, device):
    """A ``ModelRegistry`` whose text loader hands out the benchmark's
    seeded weights; the pipeline, its two programs and the residency
    ledger are the program's own. The ledger's budget is the
    configuration's ``serving.residency_budget_fraction`` of the chip
    (the operator's knob)."""
    from chiaswarm_tpu.core.mesh import device_hbm_bytes
    from chiaswarm_tpu.models.tokenizer import WordPieceTokenizer
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.text import TextComponents, word_vocab
    from chiaswarm_tpu.serving.residency import ResidencyManager

    serving = config["serving"]
    components = TextComponents(
        config=solar_config(config), model_name=f"bench/{config['name']}",
        tokenizer=WordPieceTokenizer(word_vocab(config["vocab_size"])),
        params=seeded_params(config, seed, device))

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
    return registry, components.params, components.model_name


# ---- comparison ----------------------------------------------------------


def reference_logprobs(params, config: dict, job: dict, rows,
                       precision: str = "float32"):
    """The reference's log-probability of every token of ``rows``
    (n, N) after the job's prompt."""
    from perfbench import solarref

    logits = solarref.forward_tree(
        params, solarref.sizes(config), ids_of(job["prompt"], config), rows,
        precision)
    return solarref.token_logprobs(logits, rows)


def _here(function):
    """A function of the ``textgen`` kind that reads this module's names
    (its ``reference_logprobs``, its ``check``): the same code, not a
    copy of it."""
    own = types.FunctionType(function.__code__, globals(), function.__name__,
                             function.__defaults__, function.__closure__)
    own.__kwdefaults__ = function.__kwdefaults__
    own.__doc__ = function.__doc__
    return own


check = _here(textgen.check)
control = _here(textgen.control)


# ---- the work of a job, by layer kind ------------------------------------

ITEM = 2                                                # bfloat16


def _layers(config: dict) -> dict:
    """Layer indices by kind: delta-rule, softmax, with experts, dense."""
    n = config["num_hidden_layers"]
    softmax = [i for i in range(n) if i in config["gqa_layers"]]
    dense = min(n, config["first_k_dense_replace"])
    return {"kda": [i for i in range(n) if i not in softmax],
            "softmax": softmax, "dense": list(range(dense)),
            "moe": list(range(dense, n))}


def _weights(config: dict) -> dict:
    """Parameter counts by where a step reads them."""
    d, dh = config["hidden_size"], config["head_dim"]
    linear = config["linear_attn_config"]
    h_lin, d_lin = linear["num_heads"], linear["head_dim"]
    inner = h_lin * d_lin
    rank = d_lin                # both gates' low-rank pairs (``assumed``)
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        # W_q, W_k, W_v, W_o; the two low-rank gates; W_b
        "kda": 4 * d * inner + 2 * (d * rank + rank * inner) + d * h_lin,
        # the convs, dt_bias and A_log (float32), the read-out's norm
        "kda_small": 3 * linear["short_conv_kernel_size"] * inner * ITEM
        + 4 * (inner + h_lin) + d_lin * ITEM,
        # W_q, W_gate, W_o a query head; W_k and W_v a key-value head
        "softmax": 3 * d * h * dh + 2 * d * hk * dh,
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "shared": 3 * d * config["moe_intermediate_size"]
        * config["n_shared_experts"],
        "router": d * config["published"]["n_routed_experts"],
        "head": d * config["vocab_size"]}


def _sizes(config: dict, job: dict):
    """(prompt tokens, rows, new tokens, decode steps x rows)."""
    p = len(job["prompt"].split())
    rows, new = job["num_return_sequences"], job["max_new_tokens"]
    return p, rows, new, rows * (new - 1)


def _token_flops(config: dict) -> float:
    """Operations one token needs outside the softmax layers' scores and
    the head: every projection, the recurrence (8 D^2 a head: the decay,
    the prediction, the write and the read-out of a D x D state), the
    router, the shared expert and its share of the routed ones (8 chosen
    x the share held); multiply-adds as two."""
    w, layers = _weights(config), _layers(config)
    linear = config["linear_attn_config"]
    first, past = config["experts_held"]
    held_share = (past - first) / config["published"]["n_routed_experts"]
    return 2.0 * (
        len(layers["kda"]) * w["kda"] + len(layers["softmax"]) * w["softmax"]
        + len(layers["dense"]) * w["dense_mlp"]
        + len(layers["moe"]) * (
            w["router"] + w["shared"]
            + w["expert"] * config["num_experts_per_tok"] * held_share)) \
        + len(layers["kda"]) * 8.0 * linear["num_heads"] \
        * linear["head_dim"] ** 2


def _score_flops(config: dict, pairs: float) -> float:
    """Operations of the scores and read-outs over ``pairs`` query-key
    pairs a head, summed over the softmax layers: each pair ``head_dim``
    going in and coming out at every query head."""
    return 4.0 * config["head_dim"] * config["num_attention_heads"] * pairs


def prefill_pairs(config: dict, job: dict) -> int:
    """Query-key pairs a head scores in the job's prefill, summed over
    the softmax layers: what ``chiaswarm_text_attention_pairs_total
    {prefill}`` adds for the job."""
    p = _sizes(config, job)[0]
    return len(_layers(config)["softmax"]) * p * (p + 1) // 2


def decode_pairs(config: dict, job: dict) -> int:
    """Query-key pairs a head scores in the job's decode, summed over the
    softmax layers, steps and rows: what ``chiaswarm_text_attention_pairs
    _total{decode}`` adds for the job when its rows and new tokens fill
    their buckets."""
    p, rows, new, _ = _sizes(config, job)
    steps = new - 1
    return len(_layers(config)["softmax"]) * rows * (
        steps * (p + 1) + steps * (steps - 1) // 2)


def job_flops(config: dict, job: dict) -> float:
    """Operations the job needs, multiply-adds as two: every prompt token
    through ``_token_flops`` and the softmax layers' causal half, the
    head once for the first new token, and the decode (``decode_flops``
    at the pairs the job's sizes give)."""
    p = _sizes(config, job)[0]
    return _token_flops(config) * p \
        + _score_flops(config, prefill_pairs(config, job)) \
        + 2.0 * _weights(config)["head"] \
        + decode_flops(config, job, decode_pairs(config, job))


def decode_flops(config: dict, job: dict, attention_pairs: float) -> float:
    """Operations the decode of one job needs, whatever implements it,
    multiply-adds as two: every weight outside the routed experts and
    the held share of the routed ones a row a step, the recurrence, the
    head, and the scores and read-outs over ``attention_pairs`` query-key
    pairs a head (the program's count over the softmax layers)."""
    decoded = _sizes(config, job)[3]
    return decoded * (_token_flops(config) + 2.0 * _weights(config)["head"]) \
        + _score_flops(config, attention_pairs)


def recurrence_weight_bytes(config: dict) -> int:
    """Bytes of the delta-rule layers' weights, read once a decode step
    (the scope ``recurrence`` holds every one of them)."""
    w = _weights(config)
    return len(_layers(config)["kda"]) * (ITEM * w["kda"] + w["kda_small"])


def recurrent_state_bytes(config: dict, rows: int) -> int:
    """Bytes of ``rows`` rows' float32 states and conv tails over the
    delta-rule layers."""
    linear = config["linear_attn_config"]
    inner = linear["num_heads"] * linear["head_dim"]
    return len(_layers(config)["kda"]) * rows * (
        inner * linear["head_dim"] * 4
        + 3 * (linear["short_conv_kernel_size"] - 1) * inner * ITEM)


def decode_bytes(config: dict, job: dict, experts_hit: float) -> float:
    """Bytes the decode of one job has to move between memory and the
    chip's cores, whatever implements it: at each of its ``new - 1``
    steps every weight outside the routed experts once (the head and the
    routers included), each row's recurrent state and conv tails read and
    written, the softmax layers' prompt keys and values once and each
    row's own suffix; plus the weights of the held experts that were hit
    (``experts_hit``: the program's count, summed over the job's steps
    and layers)."""
    w, layers = _weights(config), _layers(config)
    p, rows, new, _ = _sizes(config, job)
    entry = 2 * config["num_key_value_heads"] * config["head_dim"] * ITEM
    fixed = ITEM * (len(layers["softmax"]) * w["softmax"]
                    + len(layers["dense"]) * w["dense_mlp"]
                    + len(layers["moe"]) * w["shared"] + w["head"]) \
        + recurrence_weight_bytes(config) + 4 * len(layers["moe"]) * w["router"]
    state = 2 * recurrent_state_bytes(config, rows)
    caches = entry * len(layers["softmax"]) * (p + rows * new / 2.0)
    return (new - 1) * (fixed + state + caches) \
        + experts_hit * ITEM * w["expert"]


def prefill_chunks(config: dict, job: dict) -> int:
    """Executions of the prefill program one job makes."""
    return -(-_sizes(config, job)[0] // config["serving"]["prefill_chunk"])


def prefill_attention_flops(config: dict, job: dict,
                            attention_pairs: float) -> float:
    """Operations the softmax layers' attention of one job's prompt needs
    (the operations named ``causal_flash_attention``), multiply-adds as
    two, at ``attention_pairs``: the program's count
    (``chiaswarm_text_attention_pairs_total{prefill}``), the causal half
    of every softmax layer."""
    return _score_flops(config, attention_pairs)


def prefill_attention_bytes(config: dict, job: dict) -> float:
    """Bytes that attention has to move a job: every head's query and
    read-out of the prompt's tokens once a layer, the keys and values a
    chunk sees once a chunk."""
    p, chunk = _sizes(config, job)[0], config["serving"]["prefill_chunk"]
    ends = sum(min(p, (i + 1) * chunk)
               for i in range(prefill_chunks(config, job)))
    dh, hk = config["head_dim"], config["num_key_value_heads"]
    return ITEM * dh * len(_layers(config)["softmax"]) * (
        2 * p * config["num_attention_heads"] + 2 * hk * ends)


def decode_attention_flops(config: dict, job: dict) -> float:
    """Operations the softmax layers' sweep over the shared prompt needs
    a job (the operations named ``shared_prompt_attention``): every row's
    every head against the prompt's keys and values at each step."""
    p, _, _, decoded = _sizes(config, job)
    return _score_flops(config, len(_layers(config)["softmax"])
                        * decoded * p)


def decode_attention_bytes(config: dict, job: dict) -> float:
    """Bytes that sweep has to move a job: the prompt's keys and values
    once a softmax layer a step for all rows, the rows' queries in and
    their float32 read-outs and log-sum-exps out."""
    p, rows, new, decoded = _sizes(config, job)
    dh, hk = config["head_dim"], config["num_key_value_heads"]
    layers = len(_layers(config)["softmax"])
    return (new - 1) * layers * 2 * hk * dh * ITEM * p \
        + decoded * layers * config["num_attention_heads"] \
        * (dh * (ITEM + 4) + 4)


def kernel_sites(config: dict) -> list[tuple]:
    """None. The programs call two Mosaic kernels, both entries of
    ``ops/causal_flash_attention.py``: ``causal_flash_attention`` (the
    softmax layers' prefill, grouped by key-value head) and
    ``shared_prompt_attention`` (their decode over the shared prompt).
    The work of each depends on a traced offset, so no one (name, shape)
    site prices it; each has its own metric over a whole job's calls
    (``causal_flash_attention_roofline.lat``,
    ``shared_prompt_attention_roofline.lat``: the four functions above).
    The delta rule is XLA's."""
    return []


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    from perfbench import solarref

    for key in solarref.KEYS + ("vocab_size", "n_routed_experts",
                                "experts_held", "moe_intermediate_size",
                                "intermediate_size", "n_shared_experts",
                                "linear_attn_config", "left_out"):
        assert key in config, key
    linear = config["linear_attn_config"]
    assert set(solarref.LINEAR_KEYS) <= set(linear)
    assert linear["num_kv_heads"] in (None, linear["num_heads"])
    # what this stack serves: NoPE softmax layers with their gate, the
    # delta rule with b up to 2 and low-rank gates, one shared expert
    assert config["use_rope"] is False and config["use_gqa_gate"] is True
    assert config["kda_allow_neg_eigval"] is True
    assert config["kda_use_full_proj"] is False
    assert config["n_shared_experts"] == 1 and config["norm_topk_prob"]
    assert config["num_attention_heads"] % config["num_key_value_heads"] == 0
    # a cut in depth keeps the published pattern: a whole period at least
    n, layers = config["num_hidden_layers"], config["gqa_layers"]
    assert layers == sorted(layers) and layers[0] == 0
    period = layers[1] - layers[0] if len(layers) > 1 else n
    assert n >= period
    first, past = config["experts_held"]
    routed = config.get("published", {}).get("n_routed_experts",
                                             config["n_routed_experts"])
    # the chip's share: as many experts as the file counts, the router
    # never cut
    assert past - first == config["n_routed_experts"]
    assert 0 <= first < past <= routed
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
