"""The text-generation kind for an LFM2-MoE-class stack: the ``textgen``
kind's txt2txt jobs (``perfbench/kinds/textgen.py``: a prompt prefilled
in chunks, every row sampled in one scan, a JSON text artifact back)
served by ``chiaswarm_tpu/models/lfm2.py``. Owns ``perfbench/lfm2ref.py``
(the plain float32 decoder).

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
weights give the attention layers' query and key norms gains over one
(``LEAVES``: a head's softmax over 16,384 keys then sits on a few dozen,
which the rotary, the norm and the key-value head a query reads decide),
their read-out a gain (``GAINS``), the conv taps a scale at which the
tail matters, and the router's bias a spread at which it changes the
experts chosen. The head is the embedding, so the embedding has the
head's fan-in scale (logits of unit deviation). The limits are in the
configuration's file with the readings they were set from; the planted
faults are in PERF.md.
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


def lfm2_config(config: dict):
    """The program's ``Lfm2Config`` of the configuration's file: every
    width as published, the layers as cut, the experts as held."""
    import dataclasses

    from chiaswarm_tpu.models.lfm2 import Lfm2Config

    names = {f.name for f in dataclasses.fields(Lfm2Config)}
    sizes = {k: v for k, v in config.items()
             if k in names and k not in ("layer_types", "experts_held")}
    return Lfm2Config(layer_types=tuple(config["layer_types"]),
                      experts_held=tuple(config["experts_held"]),
                      dtype=config["serving"]["dtype"], **sizes)


# ---- weights and registry ------------------------------------------------

#: leaf name -> (mean, standard deviation) of a uniform fill; a kernel not
#: listed is fan-in scaled, any other name ending in ``norm`` is ones, and
#: the embedding (the head too) has the head's fan-in scale. The query and
#: key norms' gains of 1.6 give a head's scores a deviation of 1.6^2 =
#: 2.56 (64 unit products over sqrt(64)): a softmax over 16,384 keys on a
#: few dozen of them, as peaked as a trained head; with gains of one the
#: scores have unit deviation and a head reads the mean of thousands of
#: values, which no rotary, norm or key-value head changes. Conv taps of
#: 0.5 make a channel's output as much its two earlier values as its own
#: (a tail dropped or not rolled is a different function). A router bias
#: of 0.02 is about the gap between the 4th and 5th of 32 sigmoid scores:
#: it changes the 4th choice of many tokens (a bias ignored shows) and an
#: expert's share of the tokens by about a quarter.
LEAVES = {"q_norm": (1.6, 0.0), "k_norm": (1.6, 0.0),
          "conv": (0.0, 0.5), "router_bias": (0.0, 0.02)}

#: leaf name -> factor on its fan-in scale. ``wo`` x 2: an attention
#: layer's read-out (a weighted mean of a few dozen values) adds about
#: what a conv layer adds to the residual, so that a fault in one of the
#: three attention layers moves the logits.
GAINS = {"wo": 2.0}


def seeded_params(config: dict, seed: int, device):
    """The checkpoint, made on the device in one jitted call from the
    seed: the hashed counter of ``perfbench/weights.py`` under this
    kind's own scales (a stacked expert kernel's fan-in is its second to
    last axis)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.lfm2 import param_shapes

    from perfbench.weights import _MIX1, _hashed_bits, seed_words

    shapes = param_shapes(lfm2_config(config))
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    embed_std = config["hidden_size"] ** -0.5

    def fill(words):
        base = _hashed_bits(2, words[0])[1] ^ words[1]
        leaves = []
        for i, (path, spec) in enumerate(paths_leaves):
            name = path[-1].key
            if name.endswith("norm") and name not in LEAVES:
                leaves.append(jnp.ones(spec.shape, spec.dtype))
                continue
            if name == "embed":
                mean, std = 0.0, embed_std
            else:
                mean, std = LEAVES.get(name) or (
                    0.0, GAINS.get(name, 1.0) * spec.shape[-2] ** -0.5)
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
    (the operator's knob)."""
    from chiaswarm_tpu.core.mesh import device_hbm_bytes
    from chiaswarm_tpu.models.tokenizer import WordPieceTokenizer
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.text import TextComponents, word_vocab
    from chiaswarm_tpu.serving.residency import ResidencyManager

    serving = config["serving"]
    components = TextComponents(
        config=lfm2_config(config), model_name=f"bench/{config['name']}",
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
    from perfbench import lfm2ref

    logits = lfm2ref.forward_tree(
        params, lfm2ref.sizes(config), ids_of(job["prompt"], config), rows,
        precision)
    return lfm2ref.token_logprobs(logits, rows)


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
    """Layer indices by kind: conv, attention, dense MLP, experts."""
    n = config["num_hidden_layers"]
    types_ = config["layer_types"][:n]
    dense = min(n, config["num_dense_layers"])
    return {"conv": [i for i in range(n) if types_[i] == "conv"],
            "attention": [i for i in range(n)
                          if types_[i] == "full_attention"],
            "dense": list(range(dense)), "moe": list(range(dense, n))}


def _weights(config: dict) -> dict:
    """Parameter counts by where a step reads them."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        # W_in (d -> 3d) and W_out
        "conv": 4 * d * d,
        # the taps
        "conv_taps": config["conv_L_cache"] * d,
        # W_q and W_o a query head, W_k and W_v a key-value head
        "attention": 2 * d * h * dh + 2 * d * hk * dh,
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts"],
        "head": d * config["vocab_size"]}


def _sizes(config: dict, job: dict):
    """(prompt tokens, rows, new tokens, decode steps x rows)."""
    p = len(job["prompt"].split())
    rows, new = job["num_return_sequences"], job["max_new_tokens"]
    return p, rows, new, rows * (new - 1)


def _token_flops(config: dict) -> float:
    """Operations one token needs outside the attention layers' scores and
    the head: every projection, the gated conv's products and taps (B * u,
    K taps, C * v: 2K + 2 a channel), the router and the held share of
    the chosen experts (4 chosen x the share held); multiply-adds as
    two."""
    w, layers = _weights(config), _layers(config)
    first, past = config["experts_held"]
    held_share = (past - first) / config["num_experts"]
    return 2.0 * (
        len(layers["conv"]) * w["conv"]
        + len(layers["attention"]) * w["attention"]
        + len(layers["dense"]) * w["dense_mlp"]
        + len(layers["moe"]) * (
            w["router"]
            + w["expert"] * config["num_experts_per_tok"] * held_share)) \
        + len(layers["conv"]) * (2 * config["conv_L_cache"] + 2) \
        * config["hidden_size"]


def _score_flops(config: dict, pairs: float) -> float:
    """Operations of the scores and read-outs over ``pairs`` query-key
    pairs a head, summed over the attention layers: each pair
    ``head_dim`` going in and coming out at every query head."""
    return 4.0 * config["head_dim"] * config["num_attention_heads"] * pairs


def prefill_pairs(config: dict, job: dict) -> int:
    """Query-key pairs a head scores in the job's prefill, summed over
    the attention layers: what ``chiaswarm_text_attention_pairs_total
    {prefill}`` adds for the job."""
    p = _sizes(config, job)[0]
    return len(_layers(config)["attention"]) * p * (p + 1) // 2


def decode_pairs(config: dict, job: dict) -> int:
    """Query-key pairs a head scores in the job's decode, summed over the
    attention layers, steps and rows: what ``chiaswarm_text_attention_
    pairs_total{decode}`` adds for the job when its rows and new tokens
    fill their buckets."""
    p, rows, new, _ = _sizes(config, job)
    steps = new - 1
    return len(_layers(config)["attention"]) * rows * (
        steps * (p + 1) + steps * (steps - 1) // 2)


def job_flops(config: dict, job: dict) -> float:
    """Operations the job needs, multiply-adds as two: every prompt token
    through ``_token_flops`` and the attention layers' causal half, the
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
    the held share of the chosen ones a row a step, the head, and the
    scores and read-outs over ``attention_pairs`` query-key pairs a head
    (the program's count over the attention layers)."""
    decoded = _sizes(config, job)[3]
    return decoded * (_token_flops(config) + 2.0 * _weights(config)["head"]) \
        + _score_flops(config, attention_pairs)


def recurrence_weight_bytes(config: dict) -> int:
    """Bytes of the conv layers' weights, read once a decode step (the
    scope ``recurrence`` holds every one of them)."""
    w = _weights(config)
    return len(_layers(config)["conv"]) * ITEM * (w["conv"] + w["conv_taps"])


def recurrent_state_bytes(config: dict, rows: int) -> int:
    """Bytes of ``rows`` rows' conv tails over the conv layers."""
    return len(_layers(config)["conv"]) * rows \
        * (config["conv_L_cache"] - 1) * config["hidden_size"] * ITEM


def decode_bytes(config: dict, job: dict, experts_hit: float) -> float:
    """Bytes the decode of one job has to move between memory and the
    chip's cores, whatever implements it: at each of its ``new - 1``
    steps every weight outside the routed experts once (the embedding as
    the head and the routers included), each row's conv tails read and
    written, the attention layers' prompt keys and values once and each
    row's own suffix; plus the weights of the held experts that were hit
    (``experts_hit``: the program's count, summed over the job's steps
    and layers)."""
    w, layers = _weights(config), _layers(config)
    p, rows, new, _ = _sizes(config, job)
    entry = 2 * config["num_key_value_heads"] * config["head_dim"] * ITEM
    fixed = ITEM * (len(layers["attention"]) * w["attention"]
                    + len(layers["dense"]) * w["dense_mlp"] + w["head"]) \
        + recurrence_weight_bytes(config) \
        + 4 * len(layers["moe"]) * w["router"]
    state = 2 * recurrent_state_bytes(config, rows)
    caches = entry * len(layers["attention"]) * (p + rows * new / 2.0)
    return (new - 1) * (fixed + state + caches) \
        + experts_hit * ITEM * w["expert"]


def prefill_chunks(config: dict, job: dict) -> int:
    """Executions of the prefill program one job makes."""
    return -(-_sizes(config, job)[0] // config["serving"]["prefill_chunk"])


def prefill_attention_flops(config: dict, job: dict,
                            attention_pairs: float) -> float:
    """Operations the attention layers' sweep of one job's prompt needs
    (the operations named ``causal_flash_attention``), multiply-adds as
    two, at ``attention_pairs``: the program's count
    (``chiaswarm_text_attention_pairs_total{prefill}``), the causal half
    of every attention layer."""
    return _score_flops(config, attention_pairs)


def prefill_attention_bytes(config: dict, job: dict) -> float:
    """Bytes that sweep has to move a job: every head's query and
    read-out of the prompt's tokens once a layer, the keys and values a
    chunk sees once a chunk."""
    p, chunk = _sizes(config, job)[0], config["serving"]["prefill_chunk"]
    ends = sum(min(p, (i + 1) * chunk)
               for i in range(prefill_chunks(config, job)))
    dh, hk = config["head_dim"], config["num_key_value_heads"]
    return ITEM * dh * len(_layers(config)["attention"]) * (
        2 * p * config["num_attention_heads"] + 2 * hk * ends)


def decode_attention_flops(config: dict, job: dict) -> float:
    """Operations the attention layers' sweep over the shared prompt
    needs a job (the operations named ``shared_prompt_attention``): every
    row's every head against the prompt's keys and values at each
    step."""
    p, _, _, decoded = _sizes(config, job)
    return _score_flops(config, len(_layers(config)["attention"])
                        * decoded * p)


def decode_attention_bytes(config: dict, job: dict) -> float:
    """Bytes that sweep has to move a job: the prompt's keys and values
    once an attention layer a step for all rows, the rows' queries in and
    their float32 read-outs and log-sum-exps out."""
    p, rows, new, decoded = _sizes(config, job)
    dh, hk = config["head_dim"], config["num_key_value_heads"]
    layers = len(_layers(config)["attention"])
    return (new - 1) * layers * 2 * hk * dh * ITEM * p \
        + decoded * layers * config["num_attention_heads"] \
        * (dh * (ITEM + 4) + 4)


def kernel_sites(config: dict) -> list[tuple]:
    """None. The programs call two Mosaic kernels, both entries of
    ``ops/causal_flash_attention.py``: ``causal_flash_attention`` (the
    attention layers' prefill, grouped by key-value head) and
    ``shared_prompt_attention`` (their decode over the shared prompt),
    each at a head of 64 padded to the 128-lane tile. The work of each
    depends on a traced offset, so no one (name, shape) site prices it;
    each has its own metric over a whole job's calls (the four functions
    above). The gated conv is XLA's."""
    return []


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    from perfbench import lfm2ref

    for key in lfm2ref.KEYS + ("vocab_size", "num_experts", "experts_held",
                               "moe_intermediate_size", "intermediate_size",
                               "tie_word_embeddings", "published",
                               "left_out"):
        assert key in config, key
    # what this stack serves: bias-free convs, a tied head, a biased and
    # normalised router, heads that split the width
    assert config["conv_bias"] is False
    assert config["tie_word_embeddings"] is True
    assert config["use_expert_bias"] is True and config["norm_topk_prob"]
    assert config["head_dim"] \
        == config["hidden_size"] // config["num_attention_heads"]
    assert config["num_attention_heads"] % config["num_key_value_heads"] == 0
    # a cut in depth keeps the published pattern's first layers: the
    # dense ones and a whole period (an attention layer, three conv
    # layers) at least
    n, types_ = config["num_hidden_layers"], config["layer_types"]
    assert len(types_) == n
    assert types_ == config["published"]["layer_types"][:n]
    assert "full_attention" in types_[config["num_dense_layers"]:]
    assert n - config["num_dense_layers"] >= 4
    first, past = config["experts_held"]
    assert 0 <= first < past <= config["num_experts"]
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
