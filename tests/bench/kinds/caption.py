"""A second kind of job, owned by the tests: ``img2txt`` through the
worker's normal path (start image fetched from a URL, the BLIP captioner
served resident through the registry, greedy decode, ``make_text_result``),
text out, compared on logits. No cell of ``BENCHMARK.json`` uses it: it is
the proof on the CPU that a kind is files and nothing else (``perfbench/``
is not edited for it; ``tests/bench/conftest.py`` registers this module
under the name ``perfbench/kinds/__init__.py`` looks for).

The unit of work (``UNIT``) is the number of conditioning tokens of the
job's prompt (0: an unconditional caption); every job decodes the
configuration's ``serving.max_new_tokens``. The vocabulary is the
benchmark's, as the weights are: id ``i`` is the word of its base-26
digits in letters (the tokenizer splits letters from digits, so a word
is letters alone), and every special of the tokenizer sits on the stop
token's id, so that the stop token is the only one the detokenizer
swallows and every served token can be read back from the caption.

``correct``: the plain float32 reference (vision tower, then one causal
pass of the cross-attending decoder over [DEC] + prompt + the served
tokens) gives the logits at every position a token was served from; the
number is ``logit_gap``, the widest gap by which a served token's logit
lies below the reference's best. The control reads, at the same
positions, the gap of the token the reference one precision down puts
first.
"""

from __future__ import annotations

import base64
import io
import json

from perfbench import compare

UNIT = "prompt_tokens"
PROGRAM_MODULES = ("chiaswarm_tpu.pipelines.caption",)


# ---- the configuration's sizes, in the program's and in plain terms ------


def blip_config(config: dict):
    from chiaswarm_tpu.models.blip import (
        BlipConfig,
        BlipTextConfig,
        BlipVisionConfig,
    )

    text = BlipTextConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        encoder_hidden_size=config["vision"]["hidden_size"],
        layer_norm_eps=config["layer_norm_eps"],
        bos_token_id=config["bos_token_id"],
        sep_token_id=config["sep_token_id"],
        pad_token_id=config["sep_token_id"],
        dtype=config["serving"]["dtype"])
    vision = BlipVisionConfig(dtype=config["serving"]["dtype"],
                              **config["vision"])
    return BlipConfig(name=config["name"], vision=vision, text=text,
                      pixel_mean=tuple(config["pixel_mean"]),
                      pixel_std=tuple(config["pixel_std"]))


def word(i: int, config: dict) -> str:
    """``aaa``, ``aab``, ...: as many letters as the vocabulary needs."""
    letters = 1
    while 26 ** letters < config["vocab_size"]:
        letters += 1
    return "".join(chr(97 + i // 26 ** k % 26)
                   for k in reversed(range(letters)))


def vocabulary(config: dict) -> dict[str, int]:
    sep = config["sep_token_id"]
    vocab = {word(i, config): i for i in range(config["vocab_size"])
             if i != sep}
    vocab.update({name: sep for name in ("[PAD]", "[CLS]", "[SEP]",
                                         "[DEC]", "[UNK]")})
    return vocab


def image_pixels(config: dict, noise: int):
    """The job's start image, uint8 at the vision tower's own size (so
    that the pipeline's resize leaves it as it is)."""
    import numpy as np

    size = config["vision"]["image_size"]
    return np.random.RandomState(int(noise) % 2 ** 32).randint(
        0, 256, (size, size, 3)).astype(np.uint8)


def image_png(config: dict, noise: int) -> bytes:
    from PIL import Image

    out = io.BytesIO()
    Image.fromarray(image_pixels(config, noise)).save(out, format="PNG")
    return out.getvalue()


# ---- weights and registry ------------------------------------------------


def _components(config: dict):
    """The program's modules and checkpoint layout (its own random
    weights are thrown away)."""
    from chiaswarm_tpu.pipelines.caption import CaptionComponents

    return CaptionComponents.random(blip_config(config), vqa=False,
                                    model_name=f"bench/{config['name']}")


def _seeded(components, config: dict, seed: int, device):
    import jax

    from perfbench.weights import make_params

    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), components.params)
    return make_params(shapes, seed, dtype=config["serving"]["dtype"],
                       device=device)


def seeded_params(config: dict, seed: int, device):
    return _seeded(_components(config), config, seed, device)


def build(config: dict, seed: int, device):
    import dataclasses

    from chiaswarm_tpu.models.tokenizer import WordPieceTokenizer
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.caption import CaptionPipeline
    from chiaswarm_tpu.serving.residency import ResidencyManager

    components = _components(config)
    params = _seeded(components, config, seed, device)
    components = dataclasses.replace(
        components, params=params,
        tokenizer=WordPieceTokenizer(vocabulary(config)))
    max_new = int(config["serving"]["max_new_tokens"])

    class SeededRegistry(ModelRegistry):
        """The captioner's load (``caption_pipeline``'s ``build``) has no
        seam of its own: this hands the seeded components to the same
        pipeline class and the same residency ledger."""

        def caption_pipeline(self, model_name, mesh=None):
            return self.residency.acquire(
                ("caption", model_name),
                lambda: CaptionPipeline(components, max_new_tokens=max_new),
                model=model_name,
                size_of=lambda pipe: pipe.c.param_bytes(),
                priority=self._priority_for(model_name))

    registry = SeededRegistry(catalog=[{"name": components.model_name}],
                              residency=ResidencyManager())
    return registry, params, components.model_name


# ---- jobs ----------------------------------------------------------------


def job(rng, job_id: str, unit, config: dict, model_name: str) -> dict:
    serving = config["serving"]
    words = [i for i in range(config["vocab_size"])
             if i != config["sep_token_id"]]
    noise = rng.randrange(2 ** 31)
    return {
        "id": job_id,
        "model_name": model_name,
        "workflow": serving["workflow"],
        "prompt": " ".join(word(rng.choice(words), config)
                           for _ in range(int(unit))),
        "seed": noise,
        "start_image_uri": f"{serving['image_base_uri']}/{noise}.png",
    }


def job_size(job: dict) -> int:
    return len(job["prompt"].split())


# ---- the plain reference -------------------------------------------------


def _attend(q, k, v, heads, precision, bias=None):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import HIGHEST, _round

    b, l, inner = q.shape
    d = inner // heads
    q, k, v = (_round(t, precision).reshape(b, t.shape[1], heads, d)
               for t in (q, k, v))
    scores = jnp.einsum("blhd,bshd->bhls", q, k,
                        precision=HIGHEST) * d ** -0.5
    if bias is not None:
        scores = scores + bias
    weights = _round(jax.nn.softmax(scores, axis=-1), precision)
    return jnp.einsum("bhls,bshd->blhd", weights, v,
                      precision=HIGHEST).reshape(b, l, inner)


def vision_states(p, config: dict, pixels, precision):
    """(1, S, S, 3) normalised pixels -> (1, tokens, hidden)."""
    import jax.numpy as jnp

    from perfbench.reference import conv, dense, gelu_erf, layer_norm

    v = config["vision"]
    eps, heads = v.get("layer_norm_eps", 1e-5), v["num_heads"]
    patches = conv(p["patch_embedding"], pixels, precision,
                   stride=v["patch_size"], padding=0)
    patches = patches.reshape(1, -1, v["hidden_size"])
    cls = p["class_embedding"].astype(jnp.float32)[None, None]
    x = jnp.concatenate([cls, patches], axis=1)
    x = x + p["position_embedding"].astype(jnp.float32)[None, :x.shape[1]]
    for i in range(v["num_layers"]):
        lp = p[f"layers_{i}"]
        h = layer_norm(lp["layer_norm1"], x, eps)
        q, k, val = jnp.split(dense(lp["qkv"], h, precision), 3, axis=-1)
        x = x + dense(lp["projection"],
                      _attend(q, k, val, heads, precision), precision)
        h = layer_norm(lp["layer_norm2"], x, eps)
        x = x + dense(lp["fc2"], gelu_erf(dense(lp["fc1"], h, precision)),
                      precision)
    return layer_norm(p["post_layernorm"], x, eps)


def decoder_logits(p, config: dict, ids, states, precision):
    """One causal pass over ``ids`` (T,) -> logits (T, vocabulary)."""
    import jax.numpy as jnp

    from perfbench.reference import dense, gelu_erf, layer_norm

    eps, heads = config["layer_norm_eps"], config["num_attention_heads"]
    t = len(ids)
    x = p["word_embeddings"]["embedding"].astype(jnp.float32)[
        jnp.asarray(ids)][None]
    x = layer_norm(p["embed_ln"], x + p["position_embeddings"].astype(
        jnp.float32)[None, :t], eps)
    causal = jnp.triu(jnp.full((t, t), -1e9, jnp.float32), k=1)[None, None]
    for i in range(config["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        attn = _attend(dense(lp["self_query"], x, precision),
                       dense(lp["self_key"], x, precision),
                       dense(lp["self_value"], x, precision), heads,
                       precision, causal)
        x = layer_norm(lp["self_ln"],
                       x + dense(lp["self_out"], attn, precision), eps)
        attn = _attend(dense(lp["cross_query"], x, precision),
                       dense(lp["cross_key"], states, precision),
                       dense(lp["cross_value"], states, precision), heads,
                       precision)
        x = layer_norm(lp["cross_ln"],
                       x + dense(lp["cross_out"], attn, precision), eps)
        h = gelu_erf(dense(lp["intermediate"], x, precision))
        x = layer_norm(lp["output_ln"],
                       x + dense(lp["output"], h, precision), eps)
    h = layer_norm(p["head_ln"],
                   gelu_erf(dense(p["head_transform"], x, precision)), eps)
    return dense(p["decoder"], h, precision)[0]


def reference_logits(params, config: dict, job: dict, tokens: list[int],
                     precision: str = "float32"):
    """Logits at the positions the job's tokens were served from:
    (len(tokens), vocabulary), as numpy."""
    import numpy as np
    import jax.numpy as jnp

    pixels = image_pixels(config, job["seed"]).astype(np.float32) / 255.0
    mean, std = (np.asarray(config[key], np.float32)
                 for key in ("pixel_mean", "pixel_std"))
    states = vision_states(params["vision"]["params"], config,
                           jnp.asarray((pixels - mean) / std)[None],
                           precision)
    vocab = vocabulary(config)
    prefix = [config["bos_token_id"]] + [vocab[w]
                                         for w in job["prompt"].split()]
    ids = prefix + list(tokens)[:-1]
    logits = decoder_logits(params["decoder"]["params"], config, ids,
                            states, precision)
    return np.asarray(logits[len(prefix) - 1:])


# ---- comparison ----------------------------------------------------------


def served_tokens(result: dict, config: dict, job: dict) -> list[int] | None:
    """The token ids the caption was detokenized from (the stop token
    appended where the caption ended early); None if it is no caption of
    this vocabulary."""
    artifact = result["artifacts"]["primary"]
    text = json.loads(base64.b64decode(artifact["blob"]))["caption"]
    words = text.split()
    n = len(job["prompt"].split())
    if words[:n] != job["prompt"].split():
        return None
    vocab = vocabulary(config)
    max_new = int(config["serving"]["max_new_tokens"])
    if len(words) - n > max_new or not set(words[n:]) <= set(vocab):
        return None
    ids = [vocab[w] for w in words[n:]]
    return ids + [config["sep_token_id"]] * (len(ids) < max_new)


def _gap(logits, tokens) -> float:
    import numpy as np

    rows = np.arange(len(tokens))
    return float((logits.max(axis=-1) - logits[rows, tokens]).max())


def _verdict(rows: list[dict], config: dict) -> dict:
    limit = float(config["compare"]["logit_gap_limit"])
    worst = max((row["gap"] for row in rows), default=float("inf"))
    return {"ok": worst <= limit, "jobs": rows,
            "numbers": {"logit_gap": {"value": worst, "limit": limit}}}


def check(params, config: dict, good: list[dict], sent: dict, *,
          seed: int, n_jobs: int | None) -> dict:
    n_jobs = int(config["compare"]["jobs"] if n_jobs is None else n_jobs)
    rows = []
    for item in compare.pick(good, sent, seed, n_jobs, job_size):
        job = sent[item["id"]]["job"]
        tokens = served_tokens(item["result"], config, job)
        gap = float("inf") if tokens is None else _gap(
            reference_logits(params, config, job, tokens), tokens)
        rows.append({"id": item["id"], "prompt_tokens": job_size(job),
                     "tokens": tokens, "gap": gap})
    return _verdict(rows, config)


def control(params, config: dict, jobs: list[dict], *, seed: int) -> dict:
    """At each position of the tokens the float32 reference decodes
    greedily, the gap of the token the reference one precision down puts
    first."""
    precision = compare.CONTROL_OF[config["serving"]["dtype"]]
    max_new = int(config["serving"]["max_new_tokens"])
    rows = []
    for job in jobs:
        tokens: list[int] = []
        while len(tokens) < max_new and (
                not tokens or tokens[-1] != config["sep_token_id"]):
            tokens.append(int(reference_logits(
                params, config, job, tokens + [0])[-1].argmax()))
        exact = reference_logits(params, config, job, tokens)
        lower = reference_logits(params, config, job, tokens, precision)
        rows.append({"id": job["id"], "prompt_tokens": job_size(job),
                     "tokens": tokens,
                     "gap": _gap(exact, lower.argmax(axis=-1))})
    verdict = _verdict(rows, config)
    verdict["precision"] = precision
    return verdict


# ---- the work of a job ---------------------------------------------------


def job_flops(config: dict, job: dict) -> float:
    """Vision tower once, cross K/V once, then every position of prompt
    and output through the decoder and (outputs only) the head;
    multiply-adds count as two."""
    v = config["vision"]
    nv = (v["image_size"] // v["patch_size"]) ** 2 + 1
    hv, fv = v["hidden_size"], v["intermediate_size"]
    vision = 2.0 * (nv - 1) * 3 * v["patch_size"] ** 2 * hv \
        + v["num_layers"] * (2.0 * nv * (4 * hv * hv + 2 * hv * fv)
                             + 4.0 * nv * nv * hv)
    h, f = config["hidden_size"], config["intermediate_size"]
    new = int(config["serving"]["max_new_tokens"])
    total = 1 + job_size(job) + new - 1
    layers = config["num_hidden_layers"]
    decoder = layers * (
        2.0 * 2 * nv * hv * h                             # cross K/V
        + total * (2.0 * (6 * h * h + 2 * h * f) + 4.0 * nv * h)
        + 4.0 * h * total * (total + 1) / 2)              # causal self
    head = new * 2.0 * (h * h + h * config["vocab_size"])
    return vision + decoder + head


def kernel_sites(config: dict) -> list[tuple]:
    """The captioner calls no Mosaic kernel."""
    return []


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "max_position_embeddings", "layer_norm_eps",
                "bos_token_id", "sep_token_id", "vision", "pixel_mean",
                "pixel_std"):
        assert key in config, key
    # a sliced vocabulary is a smaller vocabulary: the ids the traffic
    # and the decode use lie inside it
    assert 0 <= config["bos_token_id"] < config["vocab_size"]
    assert 0 <= config["sep_token_id"] < config["vocab_size"]
    assert config["compare"]["logit_gap_limit"] > 0
    assert set(config["serving"]) == {"workflow", "dtype", "max_new_tokens",
                                      "image_base_uri"}
    assert config["serving"]["workflow"] == "img2txt"


def check_mix(mix: dict) -> None:
    counts = {int(n) for n, _ in mix[UNIT]}
    # the prompt bucket holds [DEC] + 16 conditioning tokens
    assert all(0 <= n <= 16 for n in counts)
    # 0 and any other count are two programs: both are warmed
    assert {n > 0 for n in counts} <= {int(n) > 0
                                       for n, _ in mix["warm_solo"]}
