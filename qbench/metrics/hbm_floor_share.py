"""The one-pass HBM floor of an application over its device busy time.

Every application reads and writes the whole register at least once,
whatever implements it, so the floor is 2 x state bytes / peak HBM
bytes/s (peaks.json). It reads the register's size and the peaks table
only, never the kernel list or the plan, so it cannot pass 100% and does
not go stale when a later PR changes the kernels."""


def floor_share(state_bytes: int, hbm_bytes_per_s: float,
                busy_s_per_app: float) -> float:
    return 100.0 * (2.0 * state_bytes / hbm_bytes_per_s) / busy_s_per_app


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.apps == 0 or tr.busy_s <= 0:
        return None
    return floor_share(ctx["state_bytes"], ctx["peak"]["hbm_bytes_per_s"],
                       tr.busy_s / tr.apps)
