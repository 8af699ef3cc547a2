"""``RunConfig`` — the one typed knob-set for building train steps.

The repo historically grew two vocabularies for the same family of
exchange strategies: the simulation surface (``training.TrainConfig``)
spoke ``method`` strings (``"dense" | "slgs" | "lags"``) while the
distributed surface (``launch.train.make_train_step``) spoke
``train_mode`` strings (``"dense" | "slgs" | "lags_dp" | "lags_hier"``)
plus nine loose kwargs.  ``RunConfig`` absorbs the kwarg sprawl and
:func:`canonical_mode` reconciles the string split: the canonical
vocabulary is the ``train_mode`` one, and the legacy sim-only ``"lags"``
is an alias for ``"lags_dp"`` (simulating P data-parallel workers on one
device IS the lags_dp exchange, leading-P layout).

``RunConfig`` is pure data — no jax imports, no registry lookups — so it
can be constructed anywhere (configs, CLIs, tests) without import-order
concerns.  Mode validity is checked at build time against the exchange
registry (:mod:`repro.api.registry`), not here, so third-party modes
registered later are first-class.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

#: Legacy method-string spellings -> canonical train-mode vocabulary.
MODE_ALIASES: dict[str, str] = {"lags": "lags_dp"}


def canonical_mode(mode: str) -> str:
    """Map a legacy ``method`` spelling onto the canonical mode name.

    ``"lags"`` (the sim surface's spelling) -> ``"lags_dp"``; canonical
    names pass through unchanged.  Unknown names also pass through — the
    registry lookup is the single point that rejects them, with an error
    listing what IS registered.
    """
    return MODE_ALIASES.get(mode, mode)


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Everything about HOW to train that is not the model architecture.

    One instance drives both execution surfaces: ``Session.train_step()``
    (the distributed partial-auto shard_map step) and
    ``Session.simulator()`` (the leading-P ``SimTrainer``), so a run can
    be validated in simulation and deployed distributed without
    re-translating knobs between two config types.

    ``mode=None`` / ``ratio=None`` defer to the model config's
    ``train_mode`` / ``compression_ratio`` at build time.
    """
    # exchange strategy (canonical vocabulary; legacy "lags" accepted)
    mode: str | None = None
    ratio: float | None = None
    # intra-pod (inner) tier ratio for the two-level sparse "lags_hier2"
    # mode; None = dense inner tier (ratio 1), i.e. lags_hier semantics
    ratio_inner: float | None = None
    # sim-surface pod factorization for "lags_hier2": the leading P axis
    # factors as (P // inner_workers) pods x inner_workers.  The
    # distributed surface ignores this and reads the mesh instead.
    inner_workers: int | None = None
    compressor: str = "topk_exact"
    # which implementation the exchanges select with: "xla" (lax.top_k /
    # masked-argmax HLO) or "kernel" (the Pallas TPU kernels in
    # repro.kernels — fused accumulate+select+payload-pack where
    # available; interpret mode off-TPU).  Resolved per compressor via
    # core.compressors.KERNEL_BACKED at build time on the simulation
    # surface, where not every kernel-backed compressor is exact.  On a
    # TPU the distributed block exchange (lags_dp) selects with k_b <= 256
    # in the fused kernel under either value, bitwise equal to "xla"
    # (BlockLAGSExchange._fused, core.lags.FUSED_MAX_KB).
    selection_backend: str = "xla"
    # inner-tier (intra-pod) compressor override for "lags_hier2"; None =
    # same as ``compressor``.  The inner tier selects on each worker's
    # full-size gradient, so block-parallel compressors ("topk_block")
    # belong here while the outer tier can stay exact.
    inner_compressor: str | None = None
    block_size: int = 4096
    # optional autotuned per-leaf plan (repro.autotune Schedule /
    # HierSchedule, or anything with a ``ks_tree(params_like)`` method);
    # validated against the mode/mesh via ``autotune.schedule.validate_for``
    schedule: Any = None
    # optimizer
    lr: float = 0.01
    lr_schedule: Callable[[Any], Any] | None = None   # step -> lr
    momentum: float = 0.0
    # DGC-style momentum correction: velocity accumulates BEFORE
    # sparsification.  Reaches both surfaces via
    # ``ExchangeSpec.init_extra_state`` (per-worker "mom" state).
    momentum_correction: float = 0.0
    # exchange pipelining (repro.pipeline): "off" = monolithic
    # post-backward exchange; "wave" = per-wave exchange inside backprop
    # (bitwise equal to "off"); "async1" = step-N exchange double-
    # buffered against step-N+1 compute (one step of bounded staleness)
    pipeline: str = "off"
    # optional pre-planned repro.pipeline.WaveSchedule (names are
    # re-bound at build time); None = geometry-default wave partition
    waves: Any = None
    # wave payload target in bytes; None = latency-matched default
    wave_target_bytes: int | None = None
    # compute shape
    chunk: int = 1024
    loss_chunk: int = 512
    donate: bool = True
    # instrumentation / determinism
    measure_delta: bool = False        # Eq. 20 metric, sim path only
    # online convergence health (repro.observe.health): 0 = off (zero
    # graph cost — the health reductions are gated at build time);
    # N > 0 computes per-leaf Assumption-1 delta / EF energy / staleness
    # in-graph and Session.run reads + emits them every N steps (the
    # fence cadence).  On the manual distributed surface the delta
    # numerator costs one dense psum per leaf when enabled (see README).
    health_every: int = 0
    seed: int = 0                      # PRNG stream for key-needing compressors

    def __post_init__(self):
        if self.mode is not None:
            object.__setattr__(self, "mode", canonical_mode(self.mode))
        if self.pipeline not in ("off", "wave", "async1"):
            raise ValueError(
                f"pipeline={self.pipeline!r} not in ('off', 'wave', "
                f"'async1')")
        if self.selection_backend not in ("xla", "kernel"):
            raise ValueError(
                f"selection_backend={self.selection_backend!r} not in "
                f"('xla', 'kernel')")
        if self.health_every < 0:
            raise ValueError(f"health_every={self.health_every} < 0")
        if self.pipeline == "wave" and self.momentum_correction > 0.0:
            # the wave taps form updates from raw cotangents inside
            # backprop; the DGC velocity is a post-backward recurrence
            raise ValueError(
                "momentum_correction requires pipeline 'off' or 'async1' "
                "(wave taps compute updates inside backprop)")

    def resolved_mode(self, cfg=None) -> str:
        """Canonical mode, falling back to ``cfg.train_mode``."""
        if self.mode is not None:
            return self.mode
        if cfg is not None:
            return canonical_mode(cfg.train_mode)
        return "lags_dp"

    def resolved_ratio(self, cfg=None) -> float:
        if self.ratio is not None:
            return float(self.ratio)
        if cfg is not None:
            return float(cfg.compression_ratio)
        return 250.0   # the legacy TrainConfig default

    def resolved_ratio_inner(self) -> float:
        """Inner-tier ratio (lags_hier2): ``None`` means dense (1.0)."""
        return 1.0 if self.ratio_inner is None else float(self.ratio_inner)

    def lr_at(self, step):
        """Learning rate at ``step`` (jax scalar ok) — schedule wins."""
        if self.lr_schedule is not None:
            return self.lr_schedule(step)
        return self.lr

    def key_at(self, step):
        """Per-step PRNG stream for key-needing compressors (randk).

        The ONE seed->step derivation both surfaces use, so sim and
        distributed draw identical streams for the same (seed, step);
        exchanges fold in leaf and worker indices themselves.
        """
        import jax   # lazy: keep this module importable without jax
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
