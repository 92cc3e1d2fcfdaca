"""Full-state HBM sweeps the planner makes per application (host count,
Circuit.plan_stats()["fused"]["hbm_sweeps"]): Pallas segments plus XLA
passthroughs."""


def read(ctx):
    fused = (ctx.get("plan_stats") or {}).get("fused")
    return None if fused is None else fused["hbm_sweeps"]
