"""Whether a run measured the path it claims to.  A run whose serving
took any compressed-matmul path but the fused kernels, ran any kernel as
anything but a compiled Pallas kernel, fell back down the degradation
ladder, compiled inside its window, or had requests preempted or
resumed (which the ledger's step model excludes) measured something
else, and prints no result."""
from __future__ import annotations

FUSED_PATHS = {"fused", "grouped_fused"}


def check_dispatch(dispatch: dict):
    paths = set(dispatch)
    return ("dispatch", dict(dispatch), bool(paths) and paths <= FUSED_PATHS)


def check_kernels(kernels: dict):
    return ("kernels", dict(kernels), set(kernels) == {"pallas"})


def check_fallbacks(fallbacks: dict):
    return ("fallbacks", dict(fallbacks), not fallbacks)


def check_rung(last_rung):
    return ("last_rung", last_rung, last_rung == "fused")


def check_window_compiles(n: int):
    return ("window_compiles", n, n == 0)


def check_requests(health: dict):
    moved = {k: health.get(k, 0) for k in ("preempted", "resumed", "shed",
                                           "expired", "quarantined")}
    return ("request_moves", moved, not any(moved.values()))


def failures(checks) -> list:
    return [c for c in checks if not c[2]]
