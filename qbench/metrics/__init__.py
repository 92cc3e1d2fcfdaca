"""Per-layer metric readers, one file each, found by the metric's name in
BENCHMARK.json. Each has `read(ctx)`, which returns the number, or None
when the run holds nothing to read (the harness then leaves the metric
out of the line). ctx keys: trace (trace.Summary), plan_stats,
compile_s, state_bytes, peak (this device's row of peaks.json), program
(the program's quest_tpu.profiling.Recording of a second build and
lowering of the circuit, taken after the check; run.program_record)."""
