"""quest_tpu's benchmark: BENCHMARK.json's cells on a TPU (see run.py)."""
