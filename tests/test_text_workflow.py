"""The txt2txt workflow: pipeline (pipelines/text.py), callback
(workloads/text.py), routing (node/job_args.py), residency
(node/registry.py) and an unmodified Worker against a MiniHive."""

import asyncio
import base64
import dataclasses
import json

import aiohttp
import numpy as np
import pytest

from chiaswarm_tpu.models import ling
from chiaswarm_tpu.pipelines.text import (
    TextComponents,
    TextPipeline,
    word_vocab,
)

import ling_reference as ref


@pytest.fixture(scope="module")
def pipe():
    return TextPipeline(TextComponents.random(seed=2), prefill_chunk=8,
                        max_context=32)


def words(ids):
    vocab = {i: w for w, i in word_vocab(ling.LING_TINY.vocab_size).items()}
    return " ".join(vocab[int(i)] for i in ids)


PROMPT = words(np.random.RandomState(0).randint(0, 96, 19))


def test_every_id_reads_back_from_the_text(pipe):
    ids = np.arange(96)
    assert pipe.c.tokenizer.tokenize(words(ids)) == list(ids)
    assert pipe.c.tokenizer.decode(ids) == words(ids)


def test_rows_sample_from_their_own_keys_and_repeat_by_seed(pipe):
    out = pipe(PROMPT, seed=11, max_new_tokens=6, num_return_sequences=3,
               logprobs=True)
    again = pipe(PROMPT, seed=11, max_new_tokens=6, num_return_sequences=3,
                 logprobs=True)
    assert out["sequences"] == again["sequences"]
    assert out["prompt_tokens"] == 19
    texts = [s["text"] for s in out["sequences"]]
    assert all(len(t.split()) == 6 for t in texts)
    assert len(set(texts)) > 1
    # row i of seed s draws what row 0 of seed s + i draws
    shifted = pipe(PROMPT, seed=12, max_new_tokens=6,
                   num_return_sequences=1, logprobs=True)
    assert shifted["sequences"][0]["text"] == out["sequences"][1]["text"]
    assert np.allclose(shifted["sequences"][0]["token_logprobs"],
                       out["sequences"][1]["token_logprobs"], atol=1e-5)


def test_token_logprobs_are_the_references(pipe):
    """The served log-probabilities of the sampled tokens against the
    reference's full forward over prompt + those tokens (float32 both:
    rounding, 1e-4 with room; a wrong token would be off by whole
    nats)."""
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


def counted(family, *labels):
    from chiaswarm_tpu.obs.metrics import REGISTRY

    values = REGISTRY.snapshot()[family]["values"]
    return np.array([values.get(label, 0) for label in labels])


def block_steps_counted():
    return counted("chiaswarm_text_prefill_block_steps_total", "whole",
                   "diagonal", "dead")


def kda_blocks_counted():
    return counted("chiaswarm_text_kda_blocks_total", "pairwise", "product")


def test_a_three_chunk_prompt_steps_no_block_past_the_written_cache():
    """20 tokens in chunks of 8 against a capacity of 8 chunks, one
    latent-attention layer of 2 heads, blocks of 8: chunk i steps i block
    pairs below the diagonal and one on it a head, and none of the 5 to
    8 blocks past the written cache; counted from what the host knows of
    the job."""
    pipe = TextPipeline(TextComponents.random(seed=2), prefill_chunk=8,
                        max_context=64)
    before = block_steps_counted()
    out = pipe(words(np.random.RandomState(3).randint(0, 96, 20)), seed=1,
               max_new_tokens=2)
    assert out["prompt_tokens"] == 20
    assert list(block_steps_counted() - before) == [2 * (0 + 1 + 2), 2 * 3,
                                                    0]


@pytest.mark.parametrize("tokens, whole, diagonal", [
    (5, 0, 2), (8, 0, 2), (9, 2, 4), (32, 12, 8)],
    ids=["part-of-a-chunk", "one-chunk", "into-the-second", "full"])
def test_block_steps_are_counted_per_chunk_of_the_prompt(pipe, tokens, whole,
                                                         diagonal):
    """The count follows the prompt's chunks (8 tokens, capacity 32, 2
    heads): a padded last chunk counts like a whole one, for it steps as
    many blocks."""
    zero = {k: 0 for k in ling.empty_stats()}
    before = block_steps_counted()
    pipe._count(tokens, 1, 16, zero, zero)
    assert list(block_steps_counted() - before) == [whole, diagonal, 0]


@pytest.mark.parametrize("tokens, kda_chunk, chunk, pairwise, product", [
    (5, 4, 8, 7 * 2, 0), (32, 4, 8, 7 * 4 * 2, 0),
    (16384, 64, 2048, 7168, 10752)],
    ids=["chunk4-part-of-a-chunk", "chunk4-full", "16384-tokens-the-cell"])
def test_kda_blocks_are_counted_by_form(pipe, tokens, kda_chunk, chunk,
                                        pairwise, product):
    """The delta-rule prefill's sub-blocks by how they are built, from
    host integers: at the tiny size (sub-chunks of 4) every block is
    pairwise; at the cell's sizes (sub-chunks of 64 in prefill chunks of
    2,048) a job of 16,384 tokens adds 7,168 and 10,752 (the other prompt
    lengths: ``test_ling.py``). No program runs: the count needs the
    configuration and the chunk alone."""
    components = dataclasses.replace(
        pipe.c, config=dataclasses.replace(pipe.c.config,
                                           kda_chunk=kda_chunk))
    counted = TextPipeline(components, prefill_chunk=chunk,
                           max_context=8 * chunk)
    zero = {k: 0 for k in ling.empty_stats()}
    before = kda_blocks_counted()
    counted._count(tokens, 1, 16, zero, zero)
    assert list(kda_blocks_counted() - before) == [pairwise, product]


def test_without_logprobs_the_artifact_has_text_alone(pipe):
    out = pipe(PROMPT, seed=1, max_new_tokens=3)
    assert out["sequences"] == [{"text": out["sequences"][0]["text"]}]
    assert len(out["sequences"][0]["text"].split()) == 3


@pytest.mark.parametrize("prompt,why", [
    ("", "requires a prompt"),
    ("zzzz", "outside the model's vocabulary"),
    (" ".join(["aa"] * 40), "up to 32"),
])
def test_bad_prompts_raise(pipe, prompt, why):
    with pytest.raises(ValueError, match=why):
        pipe(prompt, seed=0)


def decode_artifact(result):
    return json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))


def test_txt2txt_dispatch_through_format_args_and_the_executor():
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.node.job_args import format_args
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.workloads.text import text_callback

    registry = ModelRegistry(
        catalog=[{"name": "tiny/ling", "prefill_chunk": 8,
                  "max_context": 32}], allow_random=True)
    job = {"id": "t-1", "workflow": "txt2txt", "model_name": "tiny/ling",
           "prompt": PROMPT, "seed": 3,
           "parameters": {"max_new_tokens": 5, "num_return_sequences": 2,
                          "logprobs": True}}
    callback, kwargs = format_args(dict(job), registry)
    assert callback is text_callback
    assert kwargs["max_new_tokens"] == 5 and "parameters" not in kwargs
    result = synchronous_do_work(job, ChipPool(n_slots=1).slots[0], registry)
    config = result["pipeline_config"]
    assert "error" not in config, config
    assert config["prompt_tokens"] == 19 and config["seed"] == 3
    payload = decode_artifact(result)
    assert [len(s["text"].split()) for s in payload["sequences"]] == [5, 5]
    assert all(len(s["token_logprobs"]) == 5 for s in payload["sequences"])
    assert result["artifacts"]["primary"]["content_type"] \
        == "application/json"
    # resident: the second job reuses the pipeline the ledger holds
    assert registry.text_pipeline("tiny/ling",
                                  mesh=ChipPool(n_slots=1).slots[0].mesh) \
        is registry.text_pipeline("tiny/ling",
                                  mesh=ChipPool(n_slots=1).slots[0].mesh)
    # an error is an artifact, by img2txt's convention
    bad = synchronous_do_work(dict(job, id="t-2", prompt=""),
                              ChipPool(n_slots=1).slots[0], registry)
    assert "requires a prompt" in bad["pipeline_config"]["error"]
    assert "requires a prompt" in decode_artifact(bad)["caption"]


def test_a_node_without_the_model_says_so():
    from chiaswarm_tpu.node.registry import ModelRegistry

    with pytest.raises(ValueError, match="not available on this node"):
        ModelRegistry(catalog=[]).text_pipeline("inclusionAI/Ling")


def test_an_unmodified_worker_settles_a_txt2txt_job_from_minihive():
    """Polled, run and settled through the worker's normal path; the
    job's digest holds the four text spans under ``execute`` and the
    counters moved by what the programs returned."""
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.metrics import REGISTRY

    registry = ModelRegistry(
        catalog=[{"name": "tiny/ling", "prefill_chunk": 8,
                  "max_context": 32}], allow_random=True)

    def counters():
        snap = REGISTRY.snapshot()
        return {name: dict(snap[name]["values"]) for name in (
            "chiaswarm_text_tokens_total",
            "chiaswarm_moe_routed_pairs_total",
            "chiaswarm_moe_experts_hit_total",
            "chiaswarm_moe_layer_steps_total",
            "chiaswarm_text_prefill_block_steps_total",
            "chiaswarm_text_prefill_up_projections_total",
            "chiaswarm_text_decode_key_blocks_total",
            "chiaswarm_text_kda_blocks_total",
            "chiaswarm_text_cache_bytes")}

    async def scenario():
        hive = MiniHive(lease_s=120.0, delay_s=0.0)
        uri = await hive.start()
        worker = Worker(
            settings=Settings(
                hive_uri=uri, hive_token="t", worker_name="text",
                install_signal_handlers=False, poll_busy_s=0.02,
                poll_idle_s=0.02, drain_timeout_s=30.0,
                health_bind_ephemeral=True),
            registry=registry, pool=ChipPool(n_slots=1))
        task = asyncio.create_task(worker.run())
        try:
            hive.submit({"id": "hive-1", "workflow": "txt2txt",
                         "model_name": "tiny/ling", "prompt": PROMPT,
                         "seed": 9, "max_new_tokens": 4,
                         "num_return_sequences": 2, "logprobs": True,
                         "content_type": "application/json"})
            await hive.wait_for_results(1, timeout=300)
            host, port = worker.health_address
            async with aiohttp.ClientSession() as session:
                async with session.get(
                        f"http://{host}:{port}/metrics") as resp:
                    served = await resp.text()
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=60)
            await hive.stop()
        return hive.results[0], hive.flights.get("hive-1"), served

    before = counters()
    result, record, served = asyncio.run(scenario())
    after = counters()
    assert "error" not in result["pipeline_config"], result
    payload = decode_artifact(result)
    assert len(payload["sequences"]) == 2
    digest = record["attempts"][-1]["digest"]
    spans = {s["name"]: s for s in digest["spans"]}
    for name in ("text.tokenize", "text.prefill", "text.decode",
                 "text.detokenize"):
        assert spans[name]["phase"] == "execute" and spans[name]["dur_s"] > 0

    def moved(family, key):
        return after[family].get(key, 0) - before[family].get(key, 0)

    tokens = "chiaswarm_text_tokens_total"
    assert moved(tokens, "prefill") == 19
    assert moved(tokens, "decode") == 2 * 16    # the 16-token bucket
    pairs = "chiaswarm_moe_routed_pairs_total"
    k, layers = 4, 6
    assert moved(pairs, "prefill,yes") + moved(pairs, "prefill,no") \
        == 19 * k * layers
    assert moved(pairs, "decode,yes") + moved(pairs, "decode,no") \
        == 2 * 15 * k * layers
    hit = moved("chiaswarm_moe_experts_hit_total", "")
    assert 0 < hit <= moved(pairs, "decode,yes")
    assert moved("chiaswarm_moe_layer_steps_total", "") == 15 * layers
    for family in after:
        assert f"# TYPE {family} " in served
    assert "chiaswarm_text_prefill_key_blocks_total" not in served
    # 19 tokens in chunks of 8 against 32 slots, as grid steps of the two
    # heads' kernel: 0 + 1 + 2 block pairs a head below the diagonal,
    # three on it, and no step past the written cache
    steps = "chiaswarm_text_prefill_block_steps_total"
    assert (moved(steps, "whole"), moved(steps, "diagonal"),
            moved(steps, "dead")) == (2 * 3, 2 * 3, 0)
    assert 'chiaswarm_text_prefill_block_steps_total{kind="whole"} ' in served
    # each of those steps up-projects its key block inside the sweep: 1 +
    # 2 + 3 blocks a head, none in memory
    made = "chiaswarm_text_prefill_up_projections_total"
    assert (moved(made, "kernel"), moved(made, "memory")) == (2 * 6, 0)
    assert 'chiaswarm_text_prefill_up_projections_total{where="kernel"} ' \
        in served
    # the decode's sweep: 15 steps of one layer, the 32 slots one block
    # that holds the prompt's 19 tokens
    swept = "chiaswarm_text_decode_key_blocks_total"
    assert (moved(swept, "yes"), moved(swept, "no")) == (15, 0)
    assert 'chiaswarm_text_decode_key_blocks_total{read="yes"} ' in served
    # 19 tokens in chunks of 8, sub-chunks of 4: 7 layers x 3 x 2 blocks,
    # each the whole sub-chunk, so none is a product
    kda = "chiaswarm_text_kda_blocks_total"
    assert (moved(kda, "pairwise"), moved(kda, "product")) == (42, 0)
    assert 'chiaswarm_text_kda_blocks_total{form="pairwise"} ' in served
    assert 'chiaswarm_text_kda_blocks_total{form="product"} ' in served
    assert after["chiaswarm_text_cache_bytes"]["recurrent"] > 0
    assert after["chiaswarm_text_cache_bytes"]["latent"] \
        == (32 + 2 * 16) * ling.LING_TINY.latent_width * 4
