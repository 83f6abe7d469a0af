"""Adaptive segment-reduction strategy selection.

The engine's group-by (and the join/window count reductions that share
the segment machinery) can run on any of the interchangeable kernels in
``groupby.STRATEGIES``. Which one wins depends on the placement tier and
the shape — measured crossovers (r3/r6):

- CPU meshes (the host placement tier): packed scatter-add, always. The
  (chunk, segments) one-hot transient is pure memory-bandwidth waste on
  CPU (10M rows x 256 segments: 1.28s matmul vs 0.048s scatter).
- Accelerator meshes, small segment counts, f32 payloads: one-hot
  matmul on the MXU (scatter serializes there; measured 50x worse at
  1024 segments).
- Accelerator meshes, few segments, f64 payloads: masked sums on the VPU.
  The MXU has no float64, and the emulated f64 one-hot matmul is the
  slowest kernel there (PR 21: 5.265s vs 0.0117s in f32 at 10M rows x
  1,024 segments).
- Accelerator meshes, large segment counts: the n*num_segments one-hot
  work dominates; sorting by segment id and scattering with
  ``indices_are_sorted=True`` crosses over.

``choose_strategy`` encodes that table as the prior and sharpens it with
a ONE-SHOT on-device autotune: the first time a (platform, rows-bucket,
segments-bucket, payload-bucket, float dtype) shape is seen on a mesh,
each candidate kernel runs on a small synthetic probe, with float
payloads in the query's own accumulator dtype, placed on that mesh's
first device, and the measured winner is cached for the life of the
process.
The choice is empirical per mesh, not guessed — a v5e, a v4 and a CPU
mesh will each converge to their own table. Autotune is off on CPU
meshes by default (the prior is unambiguous and tier-1 tests run there).
"""

import math
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from fugue_tpu.jax_backend.groupby import (
    _MASKED_MAX_SEGMENTS,
    _MATMUL_MAX_SEGMENTS,
    STRATEGIES,
    segment_sums,
)

# (platform, rows_bucket, segments_bucket, payload_bucket, float dtype,
# candidates) -> measured winner. Process-lifetime cache: autotune is
# one-shot per mesh shape class, mirroring the persistent XLA compile
# cache's role.
_TUNE_CACHE: Dict[Tuple, str] = {}
# observability: how many probe sweeps actually ran (tests pin one-shot)
_TUNE_RUNS = {"count": 0}

_PROBE_MAX_ROWS = 1 << 20
_PROBE_MIN_ROWS = 1 << 14
# below this many rows a probe sweep costs more than the op it tunes
_AUTOTUNE_MIN_ROWS = 1 << 22
# below this many padded rows the all-to-all shuffle's ndev-fold padded
# receive costs more than the cross-device combine it removes
_SHUFFLE_MIN_ROWS = 1 << 15


def clear_cache() -> None:
    _TUNE_CACHE.clear()


def _bucket(x: int) -> int:
    """Power-of-two bucket: shapes within 2x share one tuning entry."""
    return 0 if x <= 1 else int(math.ceil(math.log2(x)))


def heuristic_strategy(
    platform: str, num_segments: int, n_payload: int,
    float_dtype: Any = np.float32,
) -> str:
    """The measured-table prior (used directly when autotune is off or the
    shape is too small to be worth probing). ``float_dtype`` is the float
    payloads' accumulator dtype."""
    if platform == "cpu":
        return "scatter"
    if (
        np.dtype(float_dtype) == np.float64
        and num_segments <= _MASKED_MAX_SEGMENTS
    ):
        return "masked"
    if num_segments <= _MATMUL_MAX_SEGMENTS:
        return "matmul"
    return "sort"


def autotune_enabled(
    conf_value: Any, platform: str, rows: int
) -> bool:
    """``fugue.jax.groupby.autotune``: True/False pin it; "auto" (default)
    probes only on accelerator meshes and only for frames large enough
    that one probe sweep amortizes (the CPU prior is unambiguous, and
    tier-1 tests must not pay probe compiles). Unrecognized values raise
    — a misspelled opt-out must not silently keep probing."""
    v = conf_value
    if isinstance(v, str):
        s = v.strip().lower()
        if s in ("true", "1", "always", "on"):
            return True
        if s in ("false", "0", "never", "off"):
            return False
        if s != "auto":
            raise ValueError(
                f"fugue.jax.groupby.autotune={conf_value!r} is not one of "
                "auto/true/false/on/off/always/never"
            )
    elif isinstance(v, (bool, int)):
        return bool(v)
    elif v is not None:
        raise ValueError(
            f"fugue.jax.groupby.autotune={conf_value!r} is not a "
            "bool or auto/true/false string"
        )
    return platform != "cpu" and rows >= _AUTOTUNE_MIN_ROWS


def shuffle_mode(conf_value: Any, conf_key: str) -> str:
    """Normalize a shuffle conf value to ``auto`` / ``on`` / ``off``.
    Shared by ``fugue.jax.shuffle`` and ``fugue.jax.shuffle.overlap``;
    a misspelled opt-out must not silently keep shuffling."""
    v = conf_value
    if isinstance(v, bool):
        return "on" if v else "off"
    if v is None:
        return "auto"
    s = str(v).strip().lower()
    if s in ("true", "1", "always", "on"):
        return "on"
    if s in ("false", "0", "never", "off"):
        return "off"
    if s == "auto":
        return "auto"
    raise ValueError(
        f"{conf_key}={conf_value!r} is not one of auto/on/off"
    )


def choose_shuffle(
    mode: str, mesh: Any, rows: int, num_segments: int
) -> bool:
    """The devices-aware strategy column: should this segment reduction
    repartition rows by key (all-to-all shuffle, shuffle.py) so each
    device reduces only its own segments?

    Single-device meshes never shuffle (there is nothing to co-locate).
    ``on`` forces it on any multi-device mesh; ``auto`` additionally
    requires the frame to be large enough to amortize the padded
    receive and enough segments that every device owns some."""
    ndev = int(mesh.devices.size)
    if mode == "off" or ndev <= 1 or num_segments < 1:
        return False
    if mode == "on":
        return True
    return rows >= _SHUFFLE_MIN_ROWS and num_segments >= 2 * ndev


def choose_overlap(mode: str, mesh: Any, num_segments: int) -> bool:
    """Collective/compute overlap: double-buffer the next key-range's
    all-to-all behind the current range's local reduction. Worth it
    only where collectives are asynchronous (accelerator meshes — CPU
    runs them inline, so the second pass is pure overhead) and when the
    segment space splits into two non-trivial ranges."""
    ndev = int(mesh.devices.size)
    if mode == "off" or ndev <= 1 or num_segments < 2 * ndev:
        return False
    if mode == "on":
        return True
    return mesh.devices.flat[0].platform != "cpu"


def choose_strategy(
    mesh: Any,
    rows: int,
    num_segments: int,
    n_payload: int,
    candidates: Sequence[str],
    autotune_conf: Any = "auto",
    log: Optional[Any] = None,
    float_dtype: Any = np.float32,
) -> str:
    """Pick the segment-reduction strategy for one reduction shape.

    ``candidates`` is the caller-filtered eligible subset of STRATEGIES
    (e.g. matmul family removed when exact integer sums are present).
    ``float_dtype`` is the float payloads' accumulator dtype: the probe
    runs in it, and f32 and f64 shapes keep separate entries."""
    assert len(candidates) > 0
    platform = mesh.devices.flat[0].platform
    float_dtype = np.dtype(float_dtype)
    prior = heuristic_strategy(
        platform, num_segments, n_payload, float_dtype
    )
    if prior not in candidates:
        prior = candidates[0]
    if len(candidates) == 1 or not autotune_enabled(
        autotune_conf, platform, rows
    ):
        return prior
    # the probe row count IS the cache key: probes saturate at
    # _PROBE_MAX_ROWS, so every larger frame shares one entry instead of
    # re-running a byte-identical sweep per rows bucket (review finding).
    # The saturation is a deliberate tradeoff — a 100M-row probe would
    # cost more than the op it tunes; kernel cost is ~linear in rows at
    # fixed (segments, payloads), so the 1M-row ranking carries.
    probe_n = int(min(max(rows, _PROBE_MIN_ROWS), _PROBE_MAX_ROWS))
    key = (
        platform,
        _bucket(probe_n),
        _bucket(num_segments),
        _bucket(n_payload),
        float_dtype.name,
        tuple(candidates),
    )
    hit = _TUNE_CACHE.get(key)
    if hit is not None:
        return hit
    winner = _measure(
        mesh, probe_n, num_segments, n_payload, list(candidates), prior, log,
        float_dtype,
    )
    _TUNE_CACHE[key] = winner
    return winner


def _measure(
    mesh: Any,
    n: int,
    num_segments: int,
    n_payload: int,
    candidates: List[str],
    prior: str,
    log: Optional[Any],
    float_dtype: Any,
) -> str:
    """Time each candidate kernel on an ``n``-row synthetic probe on the
    mesh's first device, float payloads in ``float_dtype``; best-of-2
    after a compile/warm run. Any failure (OOM, missing dtype support)
    falls back to the prior — tuning must never break the query."""
    import jax
    import jax.numpy as jnp

    _TUNE_RUNS["count"] += 1
    nf = max(1, n_payload - 1)
    dev = mesh.devices.flat[0]
    try:
        rng = np.random.default_rng(0)
        seg_np = rng.integers(0, max(num_segments, 1), n).astype(np.int32)
        with jax.default_device(dev):
            seg = jnp.asarray(seg_np)
            fpay = [
                jnp.asarray(rng.random(n).astype(float_dtype))
                for _ in range(nf)
            ]
            cpay = [jnp.ones((n,), jnp.bool_)]
        best, best_t = prior, float("inf")
        times: Dict[str, float] = {}

        # payloads are jit ARGUMENTS, exactly like the production call
        # sites — closure-captured constants would let XLA fold casts and
        # hoist layouts the real kernels can't, skewing the ranking
        # (review finding)
        def _run(seg_: Any, fpay_: Any, cpay_: Any, strat: str) -> Any:
            f, c, _ = segment_sums(
                fpay_, cpay_, seg_, num_segments, strategy=strat
            )
            return f, c

        for strat in candidates:
            try:
                fn = jax.jit(partial(_run, strat=strat))
                jax.block_until_ready(fn(seg, fpay, cpay))  # compile + warm
                t = float("inf")
                for _ in range(2):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(seg, fpay, cpay))
                    t = min(t, time.perf_counter() - t0)
            except Exception:  # pragma: no cover - kernel unsupported
                continue
            times[strat] = t
            if t < best_t:
                best, best_t = strat, t
        if log is not None:
            log.info(
                "fugue_tpu.jax segment-reduction autotune: %s wins at "
                "rows~%d segments=%d payloads=%d %s on %s (%.2fms; %s)",
                best, n, num_segments, n_payload, float_dtype.name,
                dev.platform, best_t * 1e3,
                " ".join(f"{k}={v * 1e3:.2f}ms" for k, v in times.items()),
            )
        return best
    except Exception:  # pragma: no cover - probe setup failed
        return prior


__all__ = [
    "STRATEGIES",
    "autotune_enabled",
    "choose_overlap",
    "choose_shuffle",
    "choose_strategy",
    "clear_cache",
    "heuristic_strategy",
    "shuffle_mode",
]
