#!/usr/bin/env python
"""CI smoke gate for the sweep-fusion layer (docs/SWEEPS.md): fails if
QFT-30 or the fusion-resistant chain benchmark regress above their
committed golden `hbm_sweeps` values, asserted CPU-side through
Circuit.plan_stats() — pure host planning, no compile, no chip.

Round 6 additions (the decoupled sweep pipeline, ISSUE 11):

  * the headline plan must report the pipeline schedule
    (`pipeline_in_slots` / `pipeline_out_slots` /
    `pipeline_overlap_steps`, with overlap >= 1 — every launch streams
    the next block under the current block's stage loop);
  * `QUEST_FUSED_PIPELINE=0` must reproduce the legacy fused record
    BIT-FOR-BIT (same keys, same values, no pipeline_* keys) — the
    silicon A/B control cannot drift;
  * the bench headline schema (bench.HEADLINE_JSON_KEYS) must carry
    the round's new keys (pipeline_*, f64_28q_*, rcs_*) so the next
    chip run lands in the BENCH_r*.json trajectory without
    hand-editing.

The goldens live HERE (the CI gate) and are mirrored by the tier-1
assertions in tests/test_sweeps.py; a planner change that moves either
must update both, consciously.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

QFT30_GOLDEN_SWEEPS = 8  # 6 until the VMEM price held a sweep to 10 wide
# phase groups (docs/KERNELS.md, "VMEM price")
CHAIN30_GOLDEN_SWEEPS = 1

# bench.py keys the trajectory parser needs for the round's deltas
REQUIRED_BENCH_KEYS = {
    "pipeline_in_slots", "pipeline_out_slots", "pipeline_overlap_steps",
    "f64_28q_peak_bytes", "f64_28q_fits_hbm", "f64_28q_chunk_elems",
    "f64_28q_value", "rcs_value", "rcs_gates_per_sec",
}


def _fused_stats(build, knob: str):
    os.environ["QUEST_FUSED_PIPELINE"] = knob
    try:
        return build().plan_stats()["fused"]
    finally:
        os.environ.pop("QUEST_FUSED_PIPELINE", None)


def main() -> int:
    import bench
    from quest_tpu.circuit import qft_circuit

    qft = qft_circuit(30).plan_stats()["fused"]
    chain = bench._build_chain_circuit(30).plan_stats()["fused"]
    # the pipeline gates below force the knob both ways; the printed
    # record reports the SAME knob-on plan the gates check, so the
    # emitted JSON always describes what was gated (an ambient
    # QUEST_FUSED_PIPELINE=0 in the environment cannot skew it)
    on = _fused_stats(lambda: bench._build_circuit(30), "1")
    rec = {
        "qft30_hbm_sweeps": qft["hbm_sweeps"],
        "qft30_stages": qft["stages"],
        "chain30_hbm_sweeps": chain["hbm_sweeps"],
        "chain30_stages": chain["stages"],
        "pipeline_in_slots": on.get("pipeline_in_slots"),
        "pipeline_out_slots": on.get("pipeline_out_slots"),
        "pipeline_overlap_steps": on.get("pipeline_overlap_steps"),
    }
    print(json.dumps(rec))
    ok = True
    if qft["hbm_sweeps"] > QFT30_GOLDEN_SWEEPS:
        print(f"REGRESSION: QFT-30 hbm_sweeps {qft['hbm_sweeps']} > "
              f"golden {QFT30_GOLDEN_SWEEPS}", file=sys.stderr)
        ok = False
    if not qft["hbm_sweeps"] < qft["stages"]:
        print("REGRESSION: QFT-30 hbm_sweeps not strictly below the "
              "per-stage pass count", file=sys.stderr)
        ok = False
    if chain["hbm_sweeps"] > CHAIN30_GOLDEN_SWEEPS:
        print(f"REGRESSION: chain hbm_sweeps {chain['hbm_sweeps']} > "
              f"golden {CHAIN30_GOLDEN_SWEEPS}", file=sys.stderr)
        ok = False
    if not 2 * chain["hbm_sweeps"] <= chain["stages"]:
        print("REGRESSION: chain sweep reduction below 2x",
              file=sys.stderr)
        ok = False

    # -- decoupled-pipeline schedule gates (ISSUE 11) -----------------
    if on.get("pipeline_overlap_steps", 0) < 1:
        print(f"REGRESSION: headline plan pipeline_overlap_steps "
              f"{on.get('pipeline_overlap_steps')} < 1 — the read "
              f"stream no longer runs ahead of compute", file=sys.stderr)
        ok = False
    if on.get("pipeline_in_slots", 0) < 2 or on.get(
            "pipeline_out_slots", 0) < 1:
        print(f"REGRESSION: pipeline slot rings degenerate "
              f"(in={on.get('pipeline_in_slots')}, "
              f"out={on.get('pipeline_out_slots')})", file=sys.stderr)
        ok = False
    off = _fused_stats(lambda: bench._build_circuit(30), "0")
    stripped = {k: v for k, v in on.items()
                if not k.startswith("pipeline_")}
    if any(k.startswith("pipeline_") for k in off):
        print("REGRESSION: QUEST_FUSED_PIPELINE=0 still reports "
              "pipeline_* keys — the legacy record drifted",
              file=sys.stderr)
        ok = False
    if off != stripped:
        print(f"REGRESSION: QUEST_FUSED_PIPELINE=0 fused record is not "
              f"bit-for-bit the knob-on record minus pipeline_* keys "
              f"(off={off}, on-minus-pipeline={stripped}) — the A/B "
              f"control plans a different schedule", file=sys.stderr)
        ok = False

    # -- bench JSON schema carries the round's keys -------------------
    missing = REQUIRED_BENCH_KEYS - bench.HEADLINE_JSON_KEYS
    if missing:
        print(f"REGRESSION: bench.HEADLINE_JSON_KEYS is missing "
              f"{sorted(missing)} — the next chip run cannot land its "
              f"deltas in the trajectory files", file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
