"""Model registry: hive catalog + resident component bundles.

Two reference behaviors merge here:

1. the server-driven model catalog (``GET /api/models`` cached to
   ``models.json``, swarm/initialize.py:97-116) whose per-model
   ``parameters`` drive dispatch (swarm/job_arguments.py:104-151), and
2. model loading — which the reference does per job from the HF cache
   (swarm/diffusion/diffusion_func.py:41-46). On TPU weights stay resident
   (core/compile_cache.py): loading + conversion + XLA compilation amortize
   across jobs, which is the single biggest architectural departure
   (SURVEY.md §7 "hard parts" #3).

Checkpoints live under ``<settings root>/models/<name with / -> __>`` in
HF-diffusers directory layout; ``allow_random=True`` (tests, benches)
fabricates random weights of the right family instead.
"""

from __future__ import annotations

import logging
from pathlib import Path
from typing import Any

from chiaswarm_tpu.models.configs import FAMILIES, ModelFamily, get_family
from chiaswarm_tpu.node.settings import load_file, settings_root
from chiaswarm_tpu.pipelines.components import Components
from chiaswarm_tpu.pipelines.diffusion import DiffusionPipeline
from chiaswarm_tpu.serving.residency import ResidencyManager, default_manager

log = logging.getLogger("chiaswarm.registry")


def model_dir(model_name: str) -> Path:
    return settings_root() / "models" / model_name.replace("/", "__")


def _mesh_cache_key(mesh) -> tuple | None:
    """Cache-key identity for a slot mesh (None -> default placement)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flatten()))


def _place_params(params, mesh, model_name: str):
    """Put a param tree where its slot executes: tensor-parallel shardings
    for >1-chip meshes, plain placement on the slot's chip otherwise, the
    default device when no slot is named. Checkpoint and host-random
    loads arrive as host numpy — left there, every jitted call would
    ship the whole tree again."""
    import jax

    if mesh is None:
        return jax.device_put(params)
    if mesh.devices.size > 1:
        from chiaswarm_tpu.parallel import shard_params

        log.info("sharding %s params over mesh %s", model_name,
                 dict(zip(mesh.axis_names, mesh.devices.shape)))
        return shard_params(params, mesh)
    device = mesh.devices.flatten()[0]
    log.info("placing %s params on %s", model_name, device)
    return jax.device_put(params, device)


class ModelRegistry:
    def __init__(self, catalog: list[dict] | None = None,
                 allow_random: bool = False,
                 attn_impl: str = "auto",
                 residency: ResidencyManager | None = None) -> None:
        if catalog is None:
            catalog = load_file("models.json") or []
        self._catalog = {m.get("name", m.get("model_name", "")): m
                         for m in catalog}
        self.allow_random = allow_random
        self.attn_impl = attn_impl
        self._quarantined: dict[str, str] = {}
        # the HBM ledger every pipeline load routes through (ISSUE 8):
        # measured footprints, priority eviction with donation, prefetch,
        # and the degradation rungs. Process-global by default (like the
        # compile cache); tests pass private managers with tiny budgets.
        self.residency = (residency if residency is not None
                          else default_manager())

    # ---- quarantine (circuit breaker, node/resilience.py) ----

    def quarantine(self, model_name: str, reason: str = "") -> None:
        """Refuse to serve ``model_name`` until :meth:`unquarantine` — the
        worker's per-model circuit breaker trips this after K consecutive
        permanent failures so one broken checkpoint cannot poison the
        whole node (it would otherwise burn a load + compile per job)."""
        log.error("quarantining model %s%s", model_name,
                  f": {reason}" if reason else "")
        self._quarantined[model_name] = reason or "circuit breaker open"
        self.residency.note_quarantined(model_name)

    def unquarantine(self, model_name: str) -> None:
        if self._quarantined.pop(model_name, None) is not None:
            log.warning("model %s released from quarantine", model_name)
        self.residency.note_unquarantined(model_name)

    def is_quarantined(self, model_name: str) -> bool:
        return model_name in self._quarantined

    def quarantined_models(self) -> list[str]:
        return sorted(self._quarantined)

    def _check_quarantine(self, model_name: str) -> None:
        reason = self._quarantined.get(model_name)
        if reason is not None:
            raise ValueError(
                f"model {model_name!r} is quarantined on this node "
                f"({reason})"
            )

    # ---- catalog (server-driven config, job_arguments.py:104-151) ----

    def entry(self, model_name: str) -> dict[str, Any]:
        return self._catalog.get(model_name, {})

    def parameters(self, model_name: str) -> dict[str, Any]:
        return dict(self.entry(model_name).get("parameters", {}))

    def known_models(self) -> list[str]:
        return list(self._catalog)

    # ---- residency (serving/residency.py is the authority) ----

    def model_states(self) -> dict[str, str]:
        """ONE authoritative per-model state enum (ISSUE 8 satellite):
        quarantine (previously a side dict) and residency (previously
        invisible) merged — ``cold`` / ``loading`` / ``resident`` /
        ``degraded`` / ``evicted`` / ``unavailable`` / ``quarantined``.
        Served at ``/healthz`` (node/worker.py)."""
        states = {name: "cold" for name in self._catalog if name}
        states.update(self.residency.model_states())
        for model in self._quarantined:
            states[model] = "quarantined"
        return states

    def lane_resident_ok(self, model_name: str) -> bool:
        """May this model pin a resident stepper lane? A model degraded
        to load-per-job must run solo (load -> run -> release) — a lane
        would hold its over-budget params live between jobs, defeating
        the rung (node/executor.py checks this BEFORE the lane submit
        path pays a transient load)."""
        return not self.residency.would_degrade(str(model_name))

    def _priority_for(self, model_name: str) -> int:
        """Catalog-driven eviction priority (higher = evicted later);
        the hive can pin its headline families hot via a
        ``residency_priority`` entry/parameter field."""
        entry = self.entry(model_name)
        raw = entry.get("residency_priority",
                        (entry.get("parameters") or {}).get(
                            "residency_priority", 0))
        try:
            return int(raw)
        except (TypeError, ValueError):
            return 0

    def _estimate_bytes(self, model_name: str) -> int | None:
        """Pre-load reservation fallback for a model never measured:
        the family estimate at the serving weight density (1 byte/param
        under CHIASWARM_WEIGHTS=int8, else bf16's 2). Replaced by the
        measured footprint after the first load."""
        try:
            from chiaswarm_tpu.convert.quantize import bytes_per_param
            from chiaswarm_tpu.pipelines.components import (
                estimate_family_bytes,
            )

            return estimate_family_bytes(self.family_for(model_name).name,
                                         bytes_per_param())
        except Exception:  # unknown family shapes: load-then-measure
            return None

    def family_for(self, model_name: str) -> ModelFamily:
        fam = self.entry(model_name).get("family")
        if fam and fam in FAMILIES:
            return FAMILIES[fam]
        return get_family(model_name)

    def _load_components(self, model_name: str) -> Components:
        ckpt = model_dir(model_name)
        if ckpt.exists():
            log.info("loading checkpoint %s from %s", model_name, ckpt)
            return Components.from_checkpoint(
                ckpt, model_name, self.family_for(model_name)
            )
        if self.allow_random:
            log.warning("no checkpoint for %s; using random weights",
                        model_name)
            return Components.random(self.family_for(model_name),
                                     model_name=model_name)
        raise ValueError(
            f"model {model_name!r} is not available on this node "
            f"(no checkpoint at {ckpt}); run `swarm-tpu init` to fetch it"
        )

    def pipeline(self, model_name: str,
                 textual_inversion: str | None = None,
                 lora: str | None = None,
                 lora_scale: float = 1.0,
                 mesh=None):
        """Resident pipeline (components + params + compiled executables),
        one measured entry in the residency ledger (serving/residency.py):
        evicting it drops the manager's strong reference to the param
        tree, and a model whose measured footprint exceeds the budget
        degrades to load-per-job instead. The pipeline class is
        selected by the family's ``kind`` ("sd" -> DiffusionPipeline,
        "upscaler" -> LatentUpscalePipeline). A textual inversion keys a
        SEPARATE entry: the concept rows merge into that entry's private
        embedding table (convert/textual_inversion.py), never the base's.
        A LoRA adapter likewise keys its own entry under
        ``(lora, lora_scale)``: the low-rank deltas merge into that
        entry's private UNet kernels once at load time
        (convert/lora.py; the runtime side-path + scale kwarg of
        swarm/diffusion/diffusion_func.py:58-68, done ahead of time so the
        jitted program and flash attention are unchanged).

        ``mesh`` (a MeshSlot's mesh) places the params: >1 chip shards
        them — Megatron-style tensor parallel on the ``model`` axis, data
        parallel batches on ``data`` (parallel/sharding.py; the pipeline
        seeds batch sharding by placing its token inputs on the ``data``
        axis) — and a single-chip slot mesh pins them to THAT chip so
        per-device slots do not all serialize on the default device.
        """
        self._check_quarantine(model_name)
        mesh_key = _mesh_cache_key(mesh)
        if mesh_key is None:
            mesh = None

        def build():
            components = self._load_components(model_name)
            if textual_inversion is not None:
                from chiaswarm_tpu.convert.textual_inversion import (
                    apply_textual_inversion,
                    load_embeddings,
                )

                ti_dir = model_dir(textual_inversion)
                if not ti_dir.exists():
                    raise ValueError(
                        f"textual inversion {textual_inversion!r} is not "
                        f"available on this node (no file at {ti_dir})"
                    )
                apply_textual_inversion(components, load_embeddings(ti_dir))
            if lora is not None:
                from chiaswarm_tpu.convert.lora import load_lora, merge_lora

                lora_dir = model_dir(lora)
                if not lora_dir.exists():
                    raise ValueError(
                        f"LoRA {lora!r} is not available on this node "
                        f"(no file at {lora_dir})"
                    )
                n_levels = len(components.family.unet.block_out_channels)
                components.params["unet"], n_merged = merge_lora(
                    components.params["unet"], load_lora(lora_dir),
                    scale=float(lora_scale), n_levels=n_levels)
                log.info("merged LoRA %s into %s (%d projections, "
                         "scale %.3g)", lora, model_name, n_merged,
                         lora_scale)
            # int8 weight residency (convert/quantize.py, gated by
            # CHIASWARM_WEIGHTS=int8 + the forward-parity tests):
            # quantize AFTER the adapter merges (fp math) and BEFORE
            # placement; multi-chip placements decline (sharding specs
            # are fp-tree-shaped)
            from chiaswarm_tpu.convert.quantize import (
                maybe_quantize_params,
            )

            components.params = maybe_quantize_params(
                components.params, family=components.family, mesh=mesh)
            # place AFTER the embedding-table/LoRA merges so the final
            # tree gets uniform placement
            components.params = _place_params(components.params, mesh,
                                              model_name)
            if components.family.kind == "upscaler":
                from chiaswarm_tpu.pipelines.upscale import (
                    LatentUpscalePipeline,
                )

                return LatentUpscalePipeline(components,
                                             attn_impl=self.attn_impl)
            if components.family.kind == "upscaler4":
                from chiaswarm_tpu.pipelines.upscale import (
                    Upscale4xPipeline,
                )

                return Upscale4xPipeline(components,
                                         attn_impl=self.attn_impl)
            return DiffusionPipeline(components, attn_impl=self.attn_impl)

        lora_key = (lora, float(lora_scale)) if lora is not None else None
        return self.residency.acquire(
            ("pipeline", model_name, textual_inversion, lora_key, mesh_key),
            build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            estimate=lambda: self._estimate_bytes(model_name),
            priority=self._priority_for(model_name),
        )

    def components(self, model_name: str) -> Components:
        return self.pipeline(model_name).c

    def cascade_pipeline(self, model_name: str, mesh=None):
        """Resident IF-class cascade (pipelines/cascade.py) — the
        ``DeepFloyd/`` dispatch target (swarm/job_arguments.py:39-40).

        Multi-chip ``mesh`` placement is tensor-parallel ONLY (weights on
        the ``model`` axis; the batch stays replicated across ``data``) —
        unlike DiffusionPipeline, the cascade does not seed its inputs on
        the ``data`` axis."""
        from chiaswarm_tpu.pipelines.cascade import (
            CascadeComponents,
            CascadePipeline,
            get_cascade_family,
        )

        self._check_quarantine(model_name)
        mesh_key = _mesh_cache_key(mesh)

        def build():
            ckpt = model_dir(model_name)
            family = get_cascade_family(model_name)
            if ckpt.exists():
                from chiaswarm_tpu.convert.torch_to_flax import (
                    load_cascade_checkpoint,
                )

                log.info("loading cascade %s from %s", model_name, ckpt)
                components = load_cascade_checkpoint(ckpt, model_name,
                                                     family)
            elif self.allow_random:
                log.warning("no checkpoint for cascade %s; using random "
                            "weights", model_name)
                components = CascadeComponents.random(family,
                                                      model_name=model_name)
            else:
                raise ValueError(
                    f"cascade model {model_name!r} is not available on this "
                    f"node (no checkpoint at {ckpt})"
                )
            components.params = _place_params(components.params, mesh,
                                              model_name)
            return CascadePipeline(components)

        return self.residency.acquire(
            ("cascade", model_name, mesh_key), build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            priority=self._priority_for(model_name),
        )

    def audio_pipeline(self, model_name: str):
        """Resident AudioLDM-class txt2audio pipeline
        (swarm/audio/audioldm.py:12-36 parity, pipelines/audio.py)."""
        from chiaswarm_tpu.pipelines.audio import (
            AudioComponents,
            AudioPipeline,
            get_audio_family,
        )

        self._check_quarantine(model_name)

        def build():
            ckpt = model_dir(model_name)
            family = get_audio_family(model_name)
            if ckpt.exists():
                from chiaswarm_tpu.convert.torch_to_flax import (
                    load_audio_checkpoint,
                )

                log.info("loading audio model %s from %s", model_name, ckpt)
                return AudioPipeline(
                    load_audio_checkpoint(ckpt, model_name, family))
            if self.allow_random:
                log.warning("no checkpoint for audio model %s; using random "
                            "weights", model_name)
                return AudioPipeline(AudioComponents.random(
                    family, model_name=model_name))
            raise ValueError(
                f"audio model {model_name!r} is not available on this node "
                f"(no checkpoint at {ckpt})"
            )

        return self.residency.acquire(
            ("audio", model_name), build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            priority=self._priority_for(model_name),
        )

    def video_pipeline(self, model_name: str, mesh=None):
        """Resident ModelScope-class txt2vid pipeline
        (swarm/video/tx2vid.py:17-57 parity, pipelines/video.py).

        Multi-chip ``mesh`` placement is tensor-parallel ONLY: temporal
        attention couples the frame axis, so frames cannot ride a
        ``data`` axis here (the frame-batched vid2vid path, which runs
        per-frame through DiffusionPipeline, does get data parallelism)."""
        from chiaswarm_tpu.pipelines.video import (
            Img2VidPipeline,
            VideoComponents,
            VideoPipeline,
            get_video_family,
        )

        self._check_quarantine(model_name)
        mesh_key = _mesh_cache_key(mesh)

        def build():
            family = get_video_family(model_name)
            pipeline_cls = (Img2VidPipeline if family.image_conditioned
                            else VideoPipeline)
            ckpt = model_dir(model_name)
            components = None
            if ckpt.exists():
                try:
                    log.info("loading video model %s from %s (strict "
                             "temporal conversion; 2D snapshots inflate "
                             "for text families only)", model_name, ckpt)
                    components = VideoComponents.from_checkpoint(
                        ckpt, model_name, family)
                except Exception as exc:
                    # truncated/partial download: fall through to the
                    # configured fallback instead of poisoning every job
                    # (same policy as tts_pipeline)
                    log.warning("video checkpoint at %s unusable (%s: %s)",
                                ckpt, type(exc).__name__, exc)
            if components is None and self.allow_random:
                log.warning("video model %s: using random weights",
                            model_name)
                components = VideoComponents.random(family,
                                                    model_name=model_name)
            if components is None:
                why = (f"checkpoint at {ckpt} is unusable"
                       if ckpt.exists() else f"no checkpoint at {ckpt}")
                raise ValueError(
                    f"video model {model_name!r} is not available on this "
                    f"node ({why})"
                )
            components.params = _place_params(components.params, mesh,
                                              model_name)
            return pipeline_cls(components, attn_impl=self.attn_impl)

        return self.residency.acquire(
            ("video", model_name, mesh_key), build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            priority=self._priority_for(model_name),
        )

    def tts_pipeline(self, model_name: str):
        """Resident bark-class TTS pipeline (swarm/audio/bark.py:11-38
        parity, pipelines/tts.py). Checkpoints load from the torch
        BarkModel layout via convert_bark."""
        from chiaswarm_tpu.pipelines.tts import (
            TTSComponents,
            TTSPipeline,
            get_tts_family,
        )

        self._check_quarantine(model_name)

        def build():
            family = get_tts_family(model_name)
            ckpt = model_dir(model_name)
            if ckpt.exists():
                try:
                    log.info("loading tts model %s from %s", model_name,
                             ckpt)
                    return TTSPipeline(TTSComponents.from_checkpoint(
                        ckpt, model_name, family))
                except Exception as exc:
                    # empty dir, truncated download (UnpicklingError),
                    # or key mismatch: fall through to the configured
                    # fallback path instead of poisoning every job
                    log.warning("tts checkpoint at %s unusable (%s: %s)",
                                ckpt, type(exc).__name__, exc)
            if self.allow_random:
                log.warning("tts model %s: using random weights", model_name)
                return TTSPipeline(TTSComponents.random(
                    family, model_name=model_name))
            raise ValueError(
                f"tts model {model_name!r} is not available on this node "
                f"(no checkpoint at {ckpt})"
            )

        return self.residency.acquire(
            ("tts", model_name), build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            priority=self._priority_for(model_name),
        )

    def caption_pipeline(self, model_name: str, mesh=None):
        """Resident BLIP-class captioner (the per-job torch BLIP load of
        swarm/captioning/caption_image.py:12-17, made resident + LRU'd;
        native stack in models/blip.py + pipelines/caption.py)."""
        from chiaswarm_tpu.pipelines.caption import (
            CaptionComponents,
            CaptionPipeline,
        )

        self._check_quarantine(model_name)
        mesh_key = _mesh_cache_key(mesh)

        def build():
            ckpt = model_dir(model_name)
            components = None
            if ckpt.exists():
                try:
                    log.info("loading caption model %s from %s", model_name,
                             ckpt)
                    components = CaptionComponents.from_checkpoint(
                        ckpt, model_name)
                except Exception as exc:
                    # same fallback policy as tts_pipeline: an unusable
                    # checkpoint dir must not poison every caption job
                    log.warning("caption checkpoint at %s unusable (%s: %s)",
                                ckpt, type(exc).__name__, exc)
            if components is None and self.allow_random:
                log.warning("no checkpoint for caption model %s; using "
                            "random tiny weights", model_name)
                components = CaptionComponents.random(
                    "blip_tiny", model_name=model_name)
            if components is None:
                why = (f"checkpoint at {ckpt} is unusable"
                       if ckpt.exists() else f"no checkpoint at {ckpt}")
                raise ValueError(
                    f"caption model {model_name!r} is not available on "
                    f"this node ({why})"
                )
            # a ~450M-param captioner gains nothing from weight sharding:
            # pin to the slot's lead chip so per-slot jobs do not all
            # serialize on the default device
            if mesh is not None:
                import jax

                device = mesh.devices.flatten()[0]
                log.info("placing %s params on %s", model_name, device)
                components.params = jax.device_put(components.params,
                                                   device)
            return CaptionPipeline(components)

        return self.residency.acquire(
            ("caption", model_name, mesh_key), build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            priority=self._priority_for(model_name),
        )

    def text_pipeline(self, model_name: str, mesh=None):
        """Resident text generator (pipelines/text.py over one of the
        stacks of models/text_stacks.py): models that take most of a
        chip (10.3-10.7 GB at the benchmark's cuts), so their entries
        are what the ledger's budget is sized around. No checkpoint
        converter exists yet: a node serves one from
        ``_load_text_components`` (seeded weights in the benchmark) or,
        under ``allow_random``, at the tiny preset of the stack its
        catalog entry names (``"stack"``; the default stack without)."""
        from chiaswarm_tpu.pipelines.text import TextPipeline

        self._check_quarantine(model_name)
        mesh_key = _mesh_cache_key(mesh)

        def build():
            components = self._load_text_components(model_name)
            if mesh is not None:
                # expert and vocabulary shares are per chip by
                # construction: pin to the slot's lead chip
                import jax

                device = mesh.devices.flatten()[0]
                log.info("placing %s params on %s", model_name, device)
                components.params = jax.device_put(components.params,
                                                   device)
            entry = self._catalog.get(model_name) or {}
            serving = {key: int(entry[key])
                       for key in ("prefill_chunk", "max_context")
                       if key in entry}
            return TextPipeline(components, **serving)

        return self.residency.acquire(
            ("text", model_name, mesh_key), build, model=model_name,
            size_of=lambda pipe: pipe.c.param_bytes(),
            priority=self._priority_for(model_name),
        )

    def _load_text_components(self, model_name: str):
        """The text model's weights and tokenizer. Overridable seam, as
        ``_load_components`` is for the diffusion families."""
        from chiaswarm_tpu.pipelines.text import TextComponents

        if self.allow_random:
            from chiaswarm_tpu.models import text_stacks

            stack = (self._catalog.get(model_name) or {}).get(
                "stack", text_stacks.DEFAULT)
            log.warning("no checkpoint loader for text model %s; using "
                        "random tiny %s weights", model_name, stack)
            return TextComponents.random(text_stacks.get(stack).TINY,
                                         model_name=model_name)
        raise ValueError(
            f"text model {model_name!r} is not available on this node "
            f"(no checkpoint at {model_dir(model_name)})")

    def controlnet(self, controlnet_name: str, family: ModelFamily,
                   mesh=None):
        """Resident ControlNetBundle (the per-job ControlNetModel load of
        swarm/diffusion/diffusion_func.py:29-34, made resident + LRU'd).

        ``mesh`` (the consuming slot's mesh) only gates the int8 path:
        sharded placements decline quantization exactly like the base
        pipeline's params, so a multi-chip generate program never mixes
        sharded fp weights with a single-device-committed int8 control
        tree. The quantization decision rides the cache key — a bundle
        requested from both a single-chip and a multi-chip slot keys
        two entries rather than serving whichever loaded first."""
        from chiaswarm_tpu.convert.quantize import int8_enabled
        from chiaswarm_tpu.pipelines.components import ControlNetBundle

        quantize = (int8_enabled() and family.kind == "sd"
                    and (mesh is None or mesh.devices.size <= 1))

        def load() -> ControlNetBundle:
            from chiaswarm_tpu.convert.quantize import (
                maybe_quantize_params,
            )

            ckpt = model_dir(controlnet_name)
            if ckpt.exists():
                log.info("loading controlnet %s from %s",
                         controlnet_name, ckpt)
                bundle = ControlNetBundle.from_checkpoint(
                    ckpt, controlnet_name, family)
            elif self.allow_random:
                log.warning("no checkpoint for controlnet %s; using random "
                            "weights", controlnet_name)
                bundle = ControlNetBundle.random(family,
                                                model_name=controlnet_name)
            else:
                raise ValueError(
                    f"controlnet {controlnet_name!r} is not available on "
                    f"this node (no checkpoint at {ckpt})"
                )
            # bundles are the catalog's multiplied checkpoint class —
            # the int8 path applies to them like the base families
            if quantize:
                bundle.params = maybe_quantize_params(
                    bundle.params, family=family, mesh=None)
            # resident, but uncommitted: one entry serves every slot
            # (the key carries no mesh), so jit moves it beside whichever
            # slot's committed params it runs with
            bundle.params = _place_params(bundle.params, None,
                                          controlnet_name)
            return bundle

        return self.residency.acquire(
            ("controlnet", controlnet_name, family.name, quantize), load,
            model=controlnet_name,
            size_of=lambda b: b.param_bytes(),
            priority=self._priority_for(controlnet_name),
        )
