"""MultiPhaseStages on the trig path, which take cos/sin of a per-element
angle sum (counter `quest.multiphase_trig`, counted per sweep kernel by
`pallas_band.compile_segment_cached` into the program's recording, which
counts none where no group is that wide). None where the run holds no
recording."""


def read(ctx):
    rec = ctx.get("program")
    return None if rec is None else rec.counts.get("quest.multiphase_trig",
                                                   0)
