"""Text generation pipeline (txt2txt): prefill in fixed chunks, then one
scan that samples every row — two resident programs a model.

The repo's first prefill/decode split. The model is a text stack
(models/text_stacks.py: a module of plain functions named by the
configuration it reads), and the caches are the stack's own carry: a
fixed-size float32 recurrent state plus a conv tail for each
linear-attention layer beside a growing latent cache for each
latent-attention layer in one stack, latent caches alone in another,
full layers' keys and values beside sliding layers' last window of them
in a third, a gated conv's tail beside full layers' keys and values in
another.

- ``text_prefill``: one chunk of ``prefill_chunk`` tokens of one row,
  the caches carried in and out, called once a chunk with the chunk's
  position and its count of real tokens as traced operands: ONE program
  for any prompt up to ``max_context`` (the last chunk may be part
  padding, which leaves the caches as they were).
- ``text_decode``: one ``lax.scan`` over ``max_new_tokens`` for
  ``num_return_sequences`` rows. A prompt's recurrent state is
  broadcast to the rows, its latents are shared by them (each row
  appends to its own suffix), and every row samples on the device
  from its own key (``core.rng.per_sample_keys``: row i of seed s draws
  what row 0 of seed s + i draws) — the static-shape, no-per-token
  dispatch design of models/gpt.py.

Host side only tokenises the prompt and reads the tokens back.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chiaswarm_tpu.core.compile_cache import (
    GLOBAL_CACHE,
    bucket_batch,
    static_cache_key,
    toplevel_jit,
)
from chiaswarm_tpu.core.rng import per_sample_keys
from chiaswarm_tpu.models import text_stacks
from chiaswarm_tpu.models.tokenizer import WordPieceTokenizer
from chiaswarm_tpu.obs import metrics
from chiaswarm_tpu.obs.trace import scope, span

#: decode lengths are compiled per power of two from here up
MIN_NEW_BUCKET = 16
MAX_NEW_TOKENS = 1024


def word_vocab(size: int) -> dict[str, int]:
    """A vocabulary of ``size`` words of letters (id i = its base-26
    digits, ``aaa``, ``aab``, ...): what a random or seeded checkpoint is
    served with, so that every id reads back from the text. The
    WordPiece specials sit past the last word, where no logit points."""
    letters = 1
    while 26 ** letters < size:
        letters += 1
    vocab = {"".join(chr(97 + i // 26 ** k % 26)
                     for k in reversed(range(letters))): i
             for i in range(size)}
    for offset, name in enumerate(("[PAD]", "[UNK]", "[CLS]", "[SEP]",
                                   "[DEC]", "[ENC]")):
        vocab[name] = size + offset
    return vocab


@dataclasses.dataclass
class TextComponents:
    config: Any             # a stack's configuration; it names its stack
    model_name: str
    tokenizer: WordPieceTokenizer
    params: dict[str, Any]

    @classmethod
    def random(cls, config=None, seed: int = 0,
               model_name: str | None = None) -> "TextComponents":
        """Random weights at ``config`` (a stack's configuration), or at
        the default stack's tiny preset."""
        if config is None:
            config = text_stacks.get(text_stacks.DEFAULT).TINY
        return cls(config=config,
                   model_name=model_name or f"random/{config.stack}_tiny",
                   tokenizer=WordPieceTokenizer(
                       word_vocab(config.vocab_size)),
                   params=text_stacks.get(config.stack).random_params(
                       config, seed))

    @property
    def stack(self):
        """The module that reads ``config`` (models/text_stacks.py)."""
        return text_stacks.get(self.config.stack)

    def param_bytes(self) -> int:
        return self.stack.param_bytes(self.params)


def _bucket_new(n: int) -> int:
    bucket = MIN_NEW_BUCKET
    while bucket < n:
        bucket *= 2
    return bucket


class TextPipeline:
    """``__call__(prompt, seed=...) -> {"sequences": [...]}``."""

    def __init__(self, components: TextComponents, *,
                 prefill_chunk: int = 2048,
                 max_context: int = 16384) -> None:
        if max_context % prefill_chunk:
            raise ValueError("max_context must be a multiple of "
                             "prefill_chunk")
        self.c = components
        self.prefill_chunk = int(prefill_chunk)
        self.max_context = int(max_context)

    # ---- the two programs ----

    def _prefill_fn(self):
        cfg, stack = self.c.config, self.c.stack

        def build():
            def text_prefill(params, ids, caches, pos, n_valid):
                return stack.prefill_chunk(params, cfg, ids, caches, pos,
                                           n_valid)

            return toplevel_jit(text_prefill)

        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "text_prefill",
                             {"chunk": self.prefill_chunk,
                              "context": self.max_context}), build)

    def _decode_fn(self, rows: int, max_new: int):
        cfg, stack = self.c.config, self.c.stack

        def build():
            @scope("sample")
            def sample(keys, logits, temperature):
                both = jax.vmap(jax.random.split)(keys)
                scaled = logits / jnp.maximum(temperature, 1e-5)
                token = jax.vmap(jax.random.categorical)(both[:, 1], scaled)
                logprob = jnp.take_along_axis(
                    jax.nn.log_softmax(logits, axis=-1), token[:, None],
                    axis=-1)[:, 0]
                return both[:, 0], token.astype(jnp.int32), logprob

            def text_decode(params, logits, caches, prompt_len, keys,
                            temperature):
                caches = stack.decode_caches(cfg, caches, rows, max_new)
                keys, first, first_lp = sample(
                    keys, jnp.broadcast_to(logits, (rows,) + logits.shape[1:]),
                    temperature)

                def body(carry, step):
                    caches, token, keys, stats = carry
                    logits, caches, s = stack.decode_step(
                        params, cfg, token, caches, prompt_len, step)
                    keys, nxt, logprob = sample(keys, logits, temperature)
                    stats = {k: stats[k] + s[k] for k in stats}
                    return (caches, nxt, keys, stats), (nxt, logprob)

                carry = (caches, first, keys, stack.empty_stats())
                carry, (tokens, logprobs) = jax.lax.scan(
                    body, carry, jnp.arange(max_new - 1, dtype=jnp.int32))
                tokens = jnp.concatenate([first[None], tokens]).T
                logprobs = jnp.concatenate([first_lp[None], logprobs]).T
                return tokens, logprobs, carry[3]

            return toplevel_jit(text_decode)

        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "text_decode",
                             {"rows": rows, "max_new": max_new,
                              "context": self.max_context}), build)

    # ---- one job ----

    def tokenize(self, prompt: str) -> np.ndarray:
        ids = np.asarray(self.c.tokenizer.tokenize(prompt), np.int32)
        if ids.size == 0:
            raise ValueError("txt2txt requires a prompt")
        if ids.size > self.max_context:
            raise ValueError(
                f"the prompt has {ids.size} tokens; this node serves "
                f"{self.c.model_name!r} up to {self.max_context}")
        if int(ids.max()) >= self.c.config.vocab_size:
            raise ValueError("the prompt holds a word outside the "
                             "model's vocabulary")
        return ids

    def prefill(self, ids: np.ndarray):
        """-> (logits after the last token (1, V), caches, stats)."""
        cfg, chunk = self.c.config, self.prefill_chunk
        fn = self._prefill_fn()
        caches = self.c.stack.empty_prefill_caches(cfg, self.max_context)
        stats = None
        for pos in range(0, len(ids), chunk):
            part = ids[pos:pos + chunk]
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :len(part)] = part
            logits, caches, s = fn(self.c.params, jnp.asarray(padded),
                                   caches, jnp.int32(pos),
                                   jnp.int32(len(part)))
            stats = s if stats is None else {k: stats[k] + s[k]
                                             for k in stats}
        return logits, caches, stats

    def __call__(self, prompt: str, *, seed: int = 0,
                 max_new_tokens: int = 128, num_return_sequences: int = 1,
                 temperature: float = 1.0,
                 logprobs: bool = False) -> dict[str, Any]:
        rows, new = int(num_return_sequences), int(max_new_tokens)
        if not 1 <= new <= MAX_NEW_TOKENS:
            raise ValueError(f"max_new_tokens must be 1..{MAX_NEW_TOKENS}")
        if rows < 1:
            raise ValueError("num_return_sequences must be at least 1")
        row_bucket, new_bucket = bucket_batch(rows), _bucket_new(new)
        t0 = time.perf_counter()
        stack = self.c.config.stack
        with span("text.tokenize", stack=stack):
            ids = self.tokenize(prompt)
        with span("text.prefill", stack=stack, tokens=int(ids.size)):
            logits, caches, prefill_stats = self.prefill(ids)
            jax.block_until_ready(logits)
        with span("text.decode", stack=stack, rows=rows, tokens=new):
            tokens, token_logprobs, stats = self._decode_fn(
                row_bucket, new_bucket)(
                    self.c.params, logits, caches, jnp.int32(ids.size),
                    per_sample_keys(seed, row_bucket),
                    jnp.float32(temperature))
            tokens = np.asarray(tokens)[:rows, :new]
            token_logprobs = np.asarray(token_logprobs)[:rows, :new]
        with span("text.detokenize", stack=stack):
            sequences = []
            for row, row_logprobs in zip(tokens, token_logprobs):
                entry = {"text": self.c.tokenizer.decode(row)}
                if logprobs:
                    entry["token_logprobs"] = [float(x)
                                               for x in row_logprobs]
                sequences.append(entry)
        self._count(int(ids.size), row_bucket, new_bucket, prefill_stats,
                    stats)
        return {"sequences": sequences,
                "prompt_tokens": int(ids.size),
                "elapsed_s": round(time.perf_counter() - t0, 3)}

    def _count(self, prompt_tokens, rows, new, prefill_stats, stats):
        """Counters from what the programs returned (no host callback
        runs inside them)."""
        metrics.TEXT_TOKENS.inc(prompt_tokens, phase="prefill")
        metrics.TEXT_TOKENS.inc(rows * new, phase="decode")
        for phase, s in (("prefill", prefill_stats), ("decode", stats)):
            held = int(s["pairs_held"])
            metrics.MOE_ROUTED_PAIRS.inc(held, phase=phase, held="yes")
            metrics.MOE_ROUTED_PAIRS.inc(int(s["pairs"]) - held,
                                         phase=phase, held="no")
        metrics.MOE_EXPERTS_HIT.inc(int(stats["experts_hit"]))
        cfg = self.c.config
        counts = self.c.stack.job_counts(cfg, prompt_tokens, rows, new,
                                         self.prefill_chunk,
                                         self.max_context)
        read, total = counts["decode_key_blocks"]
        metrics.TEXT_DECODE_KEY_BLOCKS.inc(read, read="yes")
        metrics.TEXT_DECODE_KEY_BLOCKS.inc(total - read, read="no")
        for kind, steps in counts["block_steps"].items():
            metrics.TEXT_PREFILL_BLOCK_STEPS.inc(steps, kind=kind)
        # a stack with latent-attention layers
        for where, made in counts.get("up_projections", {}).items():
            metrics.TEXT_PREFILL_UP_PROJECTIONS.inc(made, where=where)
        for phase, pairs in zip(("prefill", "decode"),
                                counts["attention_pairs"]):
            metrics.TEXT_ATTENTION_PAIRS.inc(pairs, phase=phase)
        if "kda_blocks" in counts:      # a stack with delta-rule layers
            pairwise, product = counts["kda_blocks"]
            metrics.TEXT_KDA_BLOCKS.inc(pairwise, form="pairwise")
            metrics.TEXT_KDA_BLOCKS.inc(product, form="product")
        # a stack with fixed-size state (delta-rule or gated-conv layers)
        for phase, size in counts.get("recurrent_state_bytes", {}).items():
            metrics.TEXT_RECURRENT_STATE_BYTES.inc(size, phase=phase)
        # a stack with sliding-window layers
        for kind, pairs in counts.get("window_pairs", {}).items():
            metrics.TEXT_WINDOW_PAIRS.inc(pairs, kind=kind)
        metrics.MOE_LAYER_STEPS.inc((new - 1) * counts["expert_layers"])
        for kind, size in self.c.stack.cache_bytes(
                cfg, rows, self.max_context, new).items():
            metrics.TEXT_CACHE_BYTES.set(size, kind=kind)
