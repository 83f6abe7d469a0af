"""The placement gate, copied from ``chip_smoke.py`` (PR 21) so that no
later change to the program can move it: every frame the engine holds
sits on a mesh of the expected platform, and ``engine.fallbacks`` is
empty (a host fallback is not a chip measurement)."""

from typing import Any, Iterable


class BenchFault(RuntimeError):
    """The run did not measure the chip: it must exit non-zero."""


def check_engine(engine: Any, frames: Iterable[Any], platform: str) -> None:
    meshes = [df.native.mesh for df in frames]
    meshes.append(engine.mesh)
    # every frame the engine still tracks, not only the results
    meshes.extend(b.mesh for b in list(engine._live_frames))
    platforms = sorted({d.platform for m in meshes for d in m.devices.flat})
    if platforms != [platform]:
        raise BenchFault(f"frames on {platforms}, not [{platform!r}]")
    if engine.fallbacks:
        raise BenchFault(f"engine fell back to the host: {engine.fallbacks}")
