"""Seconds of set-up in jaxpr -> MLIR lowering, Mosaic kernels included,
as the program's own recording (quest_tpu.profiling.recording) counts
them over run.program_record's second lowering: the union of JAX's
`mlir_lower` monitoring spans. None where the run holds no recording."""

from qbench import program_trace


def read(ctx):
    rec = ctx.get("program")
    return None if rec is None else program_trace.setup_split(rec)[
        "mlir_lower_s"]
