"""Text generation workload (txt2txt): a prompt in, sampled
continuations out.

No counterpart in the reference worker (PARITY.md): the hive's text-out
workflow there is img2txt alone. The job's parameters are ``prompt``,
``max_new_tokens`` (default 128), ``num_return_sequences`` (default 1),
``temperature`` (default 1.0) and ``logprobs`` (default false); the
artifact is a JSON text result ``{"sequences": [{"text",
"token_logprobs"}]}`` (``token_logprobs``: the model's own
log-probability of each sampled token, at temperature 1, when
``logprobs`` is set). Errors are swallowed into an error artifact, by
img2txt's convention (workloads/caption.py).

The model is one of the text stacks (models/text_stacks.py), named by
its catalog entry and served resident through the registry, two compiled
programs a model (pipelines/text.py); row i of a job samples from the
key of seed + i.
"""

from __future__ import annotations

from typing import Any

from chiaswarm_tpu.node.output_processor import make_text_result


def text_callback(slot, model_name: str, *, seed: int,
                  prompt: str = "",
                  max_new_tokens: int = 128,
                  num_return_sequences: int = 1,
                  temperature: float = 1.0,
                  logprobs: bool = False,
                  registry=None,
                  **_ignored: Any):
    config: dict[str, Any] = {"model_name": model_name}
    try:
        if registry is None:
            raise ValueError("txt2txt requires a model registry")
        pipeline = registry.text_pipeline(
            model_name, mesh=getattr(slot, "mesh", None))
        out = pipeline(prompt or "", seed=seed,
                       max_new_tokens=int(max_new_tokens),
                       num_return_sequences=int(num_return_sequences),
                       temperature=float(temperature),
                       logprobs=bool(logprobs))
        config["prompt_tokens"] = out["prompt_tokens"]
        config["elapsed_s"] = out["elapsed_s"]
        return {"primary": make_text_result(
            {"sequences": out["sequences"]})}, config
    except Exception as exc:  # error artifact, not a failed job
        config["error"] = str(exc)
        return {"primary": make_text_result(str(exc))}, config
