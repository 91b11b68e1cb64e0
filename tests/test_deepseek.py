"""The DeepSeek-V2-class decoder (models/deepseek.py) against the tests'
plain float32 reference (tests/deepseek_reference.py) on seeded weights
at the tiny size: hidden 64, 1 dense + 4 expert layers, latent attention
in every layer (4 heads, a query bottleneck of 24, YaRN with a kept, a
blended and an interpolated frequency pair), 16 softmax-routed experts
in 4 groups of which 4 are held, float32 weights.

Tolerances. Program and reference compute the same function in float32
in another order (chunks, absorbed products, grouped experts), so they
differ by rounding: logits of magnitude ~3 agree to a few 1e-6. The
limits sit a decade above that, and each test shows that the same
computation with bfloat16-rounded operands (relative step 2^-8) misses
its limit by a wide margin: a lower precision cannot hide inside them.
"""

import asyncio
import base64
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import deepseek, text_layers, text_stacks

import deepseek_reference as ref

CFG = deepseek.TINY
LOGIT_TOL = 5e-5      # |logit| ~ 3: a few float32 roundings, ~10x room
LAYER_TOL = 2e-5      # one layer's output, magnitude ~1


def bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


@pytest.fixture(scope="module")
def params():
    return deepseek.random_params(CFG, seed=3)


@pytest.fixture(scope="module")
def sizes():
    return ref.sizes_of(CFG)


def prefill(params, cfg, ids, chunk, capacity=64):
    caches = deepseek.empty_prefill_caches(cfg, capacity)
    fn = jax.jit(lambda p, i, c, pos, n: deepseek.prefill_chunk(
        p, cfg, i, c, pos, n))
    for pos in range(0, len(ids), chunk):
        part = np.zeros((1, chunk), np.int32)
        n = min(chunk, len(ids) - pos)
        part[0, :n] = ids[pos:pos + n]
        logits, caches, stats = fn(params, jnp.asarray(part), caches,
                                   jnp.int32(pos), jnp.int32(n))
    return logits, caches, stats


# ---- the whole stack -------------------------------------------------------


def test_prefill_then_cached_decode_is_the_full_forward(params, sizes):
    """21 prompt tokens in chunks of 8 (the last one part padding), then
    6 teacher-forced tokens on two rows through the latent cache of
    every layer: every position's logits against one uncached pass of
    the reference."""
    rng = np.random.RandomState(0)
    n_prompt, n_new = 21, 6
    ids = rng.randint(0, CFG.vocab_size, n_prompt + n_new)
    want = np.asarray(ref.forward(params, sizes, ids))
    assert np.abs(want).max() > 0.5
    logits, caches, _ = prefill(params, CFG, ids[:n_prompt], chunk=8)
    assert np.abs(np.asarray(logits[0]) - want[n_prompt - 1]).max() \
        < LOGIT_TOL
    caches = deepseek.decode_caches(CFG, caches, 2, n_new)
    step = jax.jit(lambda p, t, c, n, s: deepseek.decode_step(
        p, CFG, t, c, n, s))
    for t in range(n_new):
        token = jnp.asarray([ids[n_prompt + t]] * 2, jnp.int32)
        logits, caches, _ = step(params, token, caches,
                                 jnp.int32(n_prompt), jnp.int32(t))
        for row in range(2):
            assert np.abs(np.asarray(logits[row])
                          - want[n_prompt + t]).max() < LOGIT_TOL
    # the same pass with bfloat16-rounded weights misses the limit
    logits16, _, _ = prefill(bf16(params), CFG, ids[:n_prompt], chunk=8)
    assert np.abs(np.asarray(logits16[0]) - want[n_prompt - 1]).max() \
        > 10 * LOGIT_TOL


def test_a_whole_chunk_and_a_padded_one_give_the_same_logits(params):
    """20 tokens in chunks of 4 against the same tokens in chunks of 8
    whose last one is half padding: the padded entries lie past every
    query, so the logits and the 20 written entries are the same."""
    ids = np.random.RandomState(1).randint(0, CFG.vocab_size, 20)
    la, ca, _ = prefill(params, CFG, ids, chunk=4)
    lb, cb, _ = prefill(params, CFG, ids, chunk=8)
    assert np.abs(np.asarray(la) - np.asarray(lb)).max() < LOGIT_TOL
    for a, b in zip(ca["mla"], cb["mla"]):
        assert np.abs(np.asarray(a[:, :20])
                      - np.asarray(b[:, :20])).max() < LAYER_TOL


def test_the_layout_is_the_published_one_at_the_cut():
    """The benchmark's cut: 5 layers, 40 of 160 experts, a quarter of
    the vocabulary: 5.165 B parameters, 10.33 GB in bfloat16."""
    cfg = deepseek.DeepseekConfig(num_hidden_layers=5, vocab_size=25600,
                                  experts_held=(0, 40))
    shapes = deepseek.param_shapes(cfg)
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 5.16e9 < count < 5.17e9
    attn = shapes["layers"][3]["attn"]
    assert attn["wdq"].shape == (5120, 1536)
    assert attn["wuq"].shape == (1536, 128 * 192)
    assert attn["wdkv"].shape == (5120, 576)
    assert attn["wukv"].shape == (512, 128 * 256)
    assert attn["wo"].shape == (128 * 128, 5120)
    mlp = shapes["layers"][1]["mlp"]
    assert mlp["experts"]["gate"].shape == (40, 5120, 1536)
    assert mlp["router"].shape == (5120, 160)
    assert mlp["shared"]["up"].shape == (5120, 3072)
    assert shapes["layers"][0]["mlp"]["gate"].shape == (5120, 12288)
    assert "router_bias" not in mlp
    caches = jax.eval_shape(
        lambda: deepseek.empty_prefill_caches(cfg, 16384))
    assert [c.shape for c in caches["mla"]] == [(1, 16384, 576)] * 5
    assert deepseek.cache_bytes(cfg, 16, 16384, 64) \
        == {"latent": 5 * (16384 + 16 * 64) * 576 * 2}


# ---- latent attention ------------------------------------------------------


def test_absorbed_mla_is_the_up_projected_mla(params, sizes):
    """The decode path (key up-projection folded into the query, value
    up-projection after the softmax, latents shared and own) against the
    prefill path and against the reference's uncached layer, for the
    token that follows a 12-token prompt."""
    layer = params["layers"][2]["attn"]
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 16, CFG.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mla_layer(layer, sizes, x[0, :13]))
    width = text_layers.latent_width(CFG)
    cache = jnp.zeros((1, 32, width), jnp.float32)
    y_pre, cache = deepseek.mla_prefill(layer, CFG, x[:, :12], cache, 0)
    assert np.abs(np.asarray(y_pre[0]) - want[:12]).max() < LAYER_TOL
    suffix = jnp.zeros((3, 4, width), jnp.float32)
    y, suffix = deepseek.mla_decode(
        layer, CFG, jnp.broadcast_to(x[:, 12:13], (3, 1, CFG.hidden_size)),
        cache, jnp.int32(12), suffix, jnp.int32(0))
    assert np.abs(np.asarray(y[:, 0]) - want[12]).max() < LAYER_TOL
    # and through the prefill path at offset 12, one 4-token chunk
    y_chunk, _ = deepseek.mla_prefill(layer, CFG, x[:, 12:16], cache, 12)
    assert np.abs(np.asarray(y_chunk[0, 0]) - want[12]).max() < LAYER_TOL
    y16, _ = deepseek.mla_decode(
        bf16(layer), CFG, jnp.broadcast_to(x[:, 12:13],
                                           (3, 1, CFG.hidden_size)),
        cache, jnp.int32(12), jnp.zeros_like(suffix), jnp.int32(0))
    assert np.abs(np.asarray(y16[:, 0]) - want[12]).max() > 10 * LAYER_TOL


@pytest.mark.parametrize("fault", ["plain-rope", "no-m2", "no-q-norm"])
def test_what_this_stack_tells_the_shared_core_matters(params, sizes,
                                                       fault):
    """Plain rotary frequencies in YaRN's place, the softmax scale
    without m^2, the query bottleneck without its norm: each misses the
    reference's layer by far more than rounding."""
    layer = dict(params["layers"][1]["attn"])
    x = jnp.asarray(np.random.RandomState(9).randn(1, 24, CFG.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mla_layer(layer, sizes, x[0]))
    inv_freq = jnp.asarray(deepseek.yarn_frequencies(CFG))
    scale = deepseek.softmax_scale(CFG)
    if fault == "plain-rope":
        inv_freq = text_layers.rope_frequencies(CFG.rope_theta,
                                                CFG.qk_rope_head_dim)
    elif fault == "no-m2":
        scale = (CFG.qk_nope_head_dim + CFG.qk_rope_head_dim) ** -0.5
    else:
        layer["q_norm"] = 3.0 * layer["q_norm"]
    cache = jnp.zeros((1, 32, text_layers.latent_width(CFG)), jnp.float32)
    o, _ = text_layers.latent_prefill(
        layer, CFG, x, deepseek._query(layer, CFG, x), cache, 0,
        inv_freq=inv_freq, scale=scale,
        rope_amplitude=deepseek.rope_amplitude(CFG))
    got = np.asarray(deepseek._out(layer, x, o)[0])
    assert np.abs(got - want).max() > 100 * LAYER_TOL
    sound, _ = deepseek.mla_prefill(params["layers"][1]["attn"], CFG, x,
                                    cache, 0)
    assert np.abs(np.asarray(sound[0]) - want).max() < LAYER_TOL


def test_yarn_frequencies_and_m_for_the_published_keys():
    """Hand-computed for ``rope_scaling`` {factor 40, beta_fast 32,
    beta_slow 1, mscale = mscale_all_dim = 0.707, original 4096}, theta
    10,000 over 64 rotary dimensions: the pair that makes 32 turns in
    4096 positions is 64 ln(4096 / 64 pi) / (2 ln 10^4) = 10.47 -> 10,
    the pair that makes one 22.51 -> 23."""
    cfg = deepseek.DeepseekConfig()
    assert deepseek.yarn_band(cfg) == (10, 23)
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) \
        / (2 * math.log(1e4)) == pytest.approx(10.47, abs=0.005)
    assert 64 * math.log(4096 / (2 * math.pi)) \
        / (2 * math.log(1e4)) == pytest.approx(22.51, abs=0.005)
    freq = deepseek.yarn_frequencies(cfg)
    assert freq.shape == (32,) and freq.dtype == np.float32
    plain = 1e4 ** (-np.arange(32) / 32.0)
    # pairs 0-10 as they are, 23-31 divided by 40, pair 16 blended by
    # 6/13: f * (7/13 + 6/13 / 40)
    assert np.allclose(freq[:11], plain[:11], rtol=1e-6)
    assert np.allclose(freq[23:], plain[23:] / 40.0, rtol=1e-6)
    assert freq[16] == pytest.approx(
        1e-2 * (7 / 13 + 6 / 13 / 40), rel=1e-6)
    assert np.all(np.diff(freq) < 0)
    m = deepseek.yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert round(m, 4) == 1.2608
    assert deepseek.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    assert deepseek.rope_amplitude(cfg) == 1.0
    # the tiny preset holds every case: kept, kept, blended, interpolated
    assert deepseek.yarn_band(CFG) == (1, 3)
    assert np.allclose(deepseek.yarn_frequencies(CFG),
                       [1.0, 0.1, 0.01 * (0.5 + 0.5 / 4), 0.001 / 4])
    assert deepseek.rope_amplitude(CFG) == pytest.approx(
        (0.1 * math.log(4) + 1) / (0.0707 * math.log(4) + 1))
    got, amplitude, scale = ref.yarn(ref.sizes_of(cfg))
    assert np.allclose(got, freq, rtol=1e-6) and amplitude == 1.0
    assert scale == pytest.approx(deepseek.softmax_scale(cfg))


# ---- experts ---------------------------------------------------------------


def test_the_router_is_softmax_over_group_maxima_unnormalised(sizes):
    """Softmax over all 16, a group's score the max of its 4, the best 2
    of 4 groups kept, 3 chosen among them, weights the probabilities
    themselves x 16: against the reference's loop on ties-free inputs."""
    layer = deepseek.random_params(CFG, seed=4)["layers"][2]["mlp"]
    x = jnp.asarray(np.random.RandomState(7).randn(40, CFG.hidden_size),
                    jnp.float32)
    chosen, weight = deepseek.route(layer, CFG, x)
    with jax.default_matmul_precision("highest"):
        want_chosen, want_weight = ref.route(layer, sizes, x)
    assert np.array_equal(np.asarray(chosen), want_chosen)
    assert np.allclose(np.asarray(weight), want_weight, rtol=1e-5)
    per_group = CFG.n_routed_experts // CFG.n_group
    groups = np.asarray(chosen) // per_group
    assert all(len(set(row)) <= CFG.topk_group for row in groups)
    # not normalised: the chosen probabilities sum to less than one, and
    # to another sum in every row
    sums = np.asarray(weight).sum(-1) / CFG.routed_scaling_factor
    assert sums.max() < 1.0 and sums.std() > 0.01
    with jax.default_matmul_precision("highest"):
        probs = np.asarray(jax.nn.softmax(x @ layer["router"], -1))
    # a kept group holds the row's most probable expert
    assert np.array_equal(np.asarray(chosen)[:, 0], probs.argmax(-1))
    assert np.allclose(np.asarray(weight)[:, 0],
                       probs.max(-1) * CFG.routed_scaling_factor, rtol=1e-5)


def test_the_four_expert_shares_add_up_to_the_uncut_layer(sizes):
    """Four chips of four experts each: the parts their held experts
    give, with the shared experts (which every chip computes alike)
    counted once, are the whole layer of the uncut reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, CFG.n_routed_experts))
    layer = deepseek.random_params(whole, seed=4)["layers"][3]["mlp"]
    x = jnp.asarray(np.random.RandomState(6).randn(24, CFG.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe_layer(layer, sizes, x, held=(0, 16)))
        shared = np.asarray(ref.swiglu(layer["shared"], x))
    total = np.zeros_like(want)
    pairs_held = 0
    for first in range(0, CFG.n_routed_experts, 4):
        cfg = dataclasses.replace(CFG, experts_held=(first, first + 4))
        share = dict(layer, experts={
            name: mat[first:first + 4]
            for name, mat in layer["experts"].items()})
        y, stats = deepseek.moe(share, cfg, x)
        # one share alone is what the reference gives for that share
        with jax.default_matmul_precision("highest"):
            alone = np.asarray(ref.moe_layer(share, sizes, x,
                                             held=(first, first + 4)))
        assert np.abs(np.asarray(y) - alone).max() < LAYER_TOL
        total += np.asarray(y) - shared
        pairs_held += int(stats["pairs_held"])
        assert int(stats["pairs"]) == 24 * CFG.num_experts_per_tok
    assert np.abs(total + shared - want).max() < LAYER_TOL
    assert pairs_held == 24 * CFG.num_experts_per_tok
    # a layer that skipped its shared experts would miss by far more
    assert np.abs(total - want).max() > 100 * LAYER_TOL


# ---- the stack behind the pipeline, the registry and the worker ------------


def words(ids):
    from chiaswarm_tpu.pipelines.text import word_vocab

    vocab = {i: w for w, i in word_vocab(CFG.vocab_size).items()}
    return " ".join(vocab[int(i)] for i in ids)


PROMPT = words(np.random.RandomState(0).randint(0, 96, 19))


def test_both_stacks_are_found_by_the_name_their_configuration_gives():
    from chiaswarm_tpu.models import ling

    assert text_stacks.get(CFG.stack) is deepseek
    assert text_stacks.get(ling.LING_TINY.stack) is ling
    assert set(text_stacks.NAMES) == {"ling", "deepseek", "laguna"}
    for name in text_stacks.NAMES:
        stack = text_stacks.get(name)
        assert stack.TINY.stack == name
        for needed in ("param_shapes", "random_params", "param_bytes",
                       "empty_prefill_caches", "prefill_chunk",
                       "decode_caches", "decode_step", "empty_stats",
                       "cache_bytes", "job_counts"):
            assert callable(getattr(stack, needed)), (name, needed)
    with pytest.raises(ValueError, match="unknown text stack"):
        text_stacks.get("gpt")


def test_token_logprobs_of_the_pipeline_are_the_references():
    """The served log-probabilities of the sampled tokens against the
    reference's full forward over prompt + those tokens (float32 both:
    rounding, 1e-4 with room; a wrong token would be off by whole
    nats)."""
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    pipe = TextPipeline(TextComponents.random(deepseek.TINY, seed=2),
                        prefill_chunk=8, max_context=32)
    assert pipe.c.stack is deepseek
    assert pipe.c.model_name == "random/deepseek_tiny"
    out = pipe(PROMPT, seed=5, max_new_tokens=7, num_return_sequences=2,
               logprobs=True)
    prompt_ids = pipe.tokenize(PROMPT)
    sizes = ref.sizes_of(pipe.c.config)
    for seq in out["sequences"]:
        new = pipe.c.tokenizer.tokenize(seq["text"])
        assert len(new) == len(seq["token_logprobs"]) == 7
        logits = np.asarray(ref.forward(
            pipe.c.params, sizes, np.concatenate([prompt_ids, new])),
            np.float64)[len(prompt_ids) - 1:-1]
        norm = np.log(np.exp(logits).sum(-1))
        want = logits[np.arange(7), new] - norm
        assert np.abs(want - np.asarray(seq["token_logprobs"])).max() < 1e-4


def test_attention_pairs_are_counted_from_host_integers():
    """A prompt token sees the tokens up to itself; a decode step sees
    the prompt and the suffix up to its own entry; per latent-attention
    layer (5 here, 1 in the other stack's tiny preset)."""
    from chiaswarm_tpu.models import ling

    counts = deepseek.job_counts(CFG, 19, 2, 16, 8, 32)
    assert counts["attention_pairs"] == (
        5 * 19 * 20 // 2, 5 * 2 * sum(19 + s + 1 for s in range(15)))
    assert counts["expert_layers"] == 4 and "kda_blocks" not in counts
    assert counts["key_blocks"] == (5 * 6, 5 * 12)
    theirs = ling.job_counts(ling.LING_TINY, 19, 2, 16, 8, 32)
    assert theirs["attention_pairs"] == (
        19 * 20 // 2, 2 * sum(19 + s + 1 for s in range(15)))
    assert theirs["kda_blocks"] == (42, 0) and theirs["expert_layers"] == 6
    # the cell's job: 16,384 tokens, 16 rows x 64 new, 5 layers
    big = deepseek.DeepseekConfig(num_hidden_layers=5)
    prefill_pairs, decode_pairs = deepseek.job_counts(
        big, 16384, 16, 64, 2048, 16384)["attention_pairs"]
    assert prefill_pairs == 5 * 16384 * 16385 // 2
    assert decode_pairs == 5 * 16 * (63 * 16385 + 63 * 62 // 2)


def test_an_unmodified_worker_settles_a_txt2txt_job_of_the_second_stack():
    """Polled, run and settled through the worker's normal path; the
    catalog entry names the stack, the four text spans carry it, and the
    counter families moved by what this stack's programs returned (the
    delta-rule family did not: the stack has no such layer)."""
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.metrics import REGISTRY

    registry = ModelRegistry(
        catalog=[{"name": "tiny/deepseek", "stack": "deepseek",
                  "prefill_chunk": 8, "max_context": 32}],
        allow_random=True)

    def counters():
        snap = REGISTRY.snapshot()
        return {name: dict(snap[name]["values"]) for name in (
            "chiaswarm_text_tokens_total",
            "chiaswarm_moe_routed_pairs_total",
            "chiaswarm_moe_experts_hit_total",
            "chiaswarm_moe_layer_steps_total",
            "chiaswarm_text_prefill_key_blocks_total",
            "chiaswarm_text_attention_pairs_total",
            "chiaswarm_text_kda_blocks_total",
            "chiaswarm_text_cache_bytes")}

    async def scenario():
        hive = MiniHive(lease_s=120.0, delay_s=0.0)
        uri = await hive.start()
        worker = Worker(
            settings=Settings(
                hive_uri=uri, hive_token="t", worker_name="text",
                install_signal_handlers=False, poll_busy_s=0.02,
                poll_idle_s=0.02, drain_timeout_s=30.0),
            registry=registry, pool=ChipPool(n_slots=1))
        task = asyncio.create_task(worker.run())
        try:
            hive.submit({"id": "hive-ds", "workflow": "txt2txt",
                         "model_name": "tiny/deepseek", "prompt": PROMPT,
                         "seed": 9, "max_new_tokens": 4,
                         "num_return_sequences": 2, "logprobs": True,
                         "content_type": "application/json"})
            await hive.wait_for_results(1, timeout=300)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=60)
            await hive.stop()
        return hive.results[0], hive.flights.get("hive-ds")

    before = counters()
    result, record = asyncio.run(scenario())
    after = counters()
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert len(payload["sequences"]) == 2
    assert all(len(s["token_logprobs"]) == 4 for s in payload["sequences"])
    assert registry.text_pipeline("tiny/deepseek").c.stack is deepseek
    spans = {s["name"]: s
             for s in record["attempts"][-1]["digest"]["spans"]}
    for name in ("text.tokenize", "text.prefill", "text.decode",
                 "text.detokenize"):
        assert spans[name]["phase"] == "execute" and spans[name]["dur_s"] > 0

    def moved(family, key):
        return after[family].get(key, 0) - before[family].get(key, 0)

    tokens = "chiaswarm_text_tokens_total"
    assert moved(tokens, "prefill") == 19
    assert moved(tokens, "decode") == 2 * 16    # the 16-token bucket
    pairs = "chiaswarm_moe_routed_pairs_total"
    k, layers = CFG.num_experts_per_tok, 4
    assert moved(pairs, "prefill,yes") + moved(pairs, "prefill,no") \
        == 19 * k * layers
    assert moved(pairs, "decode,yes") + moved(pairs, "decode,no") \
        == 2 * 15 * k * layers
    hit = moved("chiaswarm_moe_experts_hit_total", "")
    assert 0 < hit <= moved(pairs, "decode,yes")
    assert moved("chiaswarm_moe_layer_steps_total", "") == 15 * layers
    blocks = "chiaswarm_text_prefill_key_blocks_total"
    assert (moved(blocks, "yes"), moved(blocks, "no")) == (5 * 6, 5 * 6)
    seen = "chiaswarm_text_attention_pairs_total"
    assert moved(seen, "prefill") == 5 * 190
    assert moved(seen, "decode") == 5 * 2 * sum(20 + s for s in range(15))
    kda = "chiaswarm_text_kda_blocks_total"
    assert (moved(kda, "pairwise"), moved(kda, "product")) == (0, 0)
    assert after["chiaswarm_text_cache_bytes"]["latent"] \
        == 5 * (32 + 2 * 16) * text_layers.latent_width(CFG) * 4


def test_the_spans_carry_the_stacks_name():
    from chiaswarm_tpu.obs.trace import JobTrace
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    pipe = TextPipeline(TextComponents.random(deepseek.TINY, seed=2),
                        prefill_chunk=8, max_context=32)
    trace = JobTrace()
    with trace.active():
        pipe(PROMPT, seed=1, max_new_tokens=2)
    by_name = {s.name: s for s in trace.root.children}
    for name in ("text.tokenize", "text.prefill", "text.decode",
                 "text.detokenize"):
        assert by_name[name].meta["stack"] == "deepseek"


def test_the_smoke_job_of_the_second_stack_settles():
    """``python -m chiaswarm_tpu.node.smoke --workflow txt2txt_deepseek``:
    the hard-coded job through the real dispatch path, the stack named by
    the smoke registry's catalog entry."""
    from chiaswarm_tpu.node.smoke import run_smoke

    result = run_smoke("txt2txt_deepseek")
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert [len(s["text"].split()) for s in payload["sequences"]] == [4, 4]
