"""fused_stats_roofline: the least time one kernel call could take on
this chip over the time a call took (mean per call, slowest device).

Least time = max(FLOPs / peak FLOP/s, bytes / HBM bandwidth) for the
work the call requires over its n rows of width K: FLOPs are Sigma's
lower triangle n K (K+1) plus margins and b 4 n K; bytes are one read of
X at 4 bytes a value (the float32 the entry point receives), five per-row
vectors read or written once (rho, beta, mask in; margin, gamma out),
w read and b written (4 K each) and Sigma written once (4 K^2). A later
narrower X stream that still passes ``correct`` needs this count
revisited by a benchmark change.
"""


def call_flops(n: int, k: int) -> float:
    return float(n) * k * (k + 1) + 4.0 * n * k


def call_bytes(n: int, k: int) -> float:
    return 4.0 * n * k + 4.0 * 5 * n + 4.0 * 2 * k + 4.0 * k * k


def least_seconds(n: int, k: int, peak) -> tuple[float, str]:
    """(least time of one call, which bound sets it)."""
    t_flops = call_flops(n, k) / peak.flops_per_s
    t_bytes = call_bytes(n, k) / peak.hbm_bytes_per_s
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops,
                                                           "compute")


def read(ctx):
    calls = [(s / n) for s, n in
             ctx.metric("fused_stats_ms").per_device(ctx).values() if n]
    if not calls:
        return None
    least, _ = least_seconds(ctx.job.rows_per_chip, ctx.job.width, ctx.peak)
    return 100.0 * least / max(calls)
