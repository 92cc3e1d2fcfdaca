"""Sweeps the planner's VMEM price cut (counter `quest.sweep_vmem_cut`,
counted in `pallas_band.sweep_plan` inside the `quest.plan` span of the
program's recording): merges that every other budget allowed and the
price of the stage chain refused. None where the recording holds no such
counter."""


def read(ctx):
    rec = ctx.get("program")
    return None if rec is None else rec.counts.get("quest.sweep_vmem_cut")
