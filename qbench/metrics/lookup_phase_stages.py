"""Wide phase groups on the block-local lookup path, which take cos/sin
of a per-step table instead of a per-element angle sum (counter
`quest.multiphase_lookup`, counted per sweep kernel by
`pallas_band.compile_segment_cached` into the program's recording). None
where the recording holds no such counter: a program without the path,
or a plan with no group it takes."""


def read(ctx):
    rec = ctx.get("program")
    return None if rec is None else rec.counts.get("quest.multiphase_lookup")
