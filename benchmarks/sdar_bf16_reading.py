"""The second reading behind every limit of ``chipbench/drivers/sdar_train.py``'s
comparison (PERF.md section 4): the cell run as it is, but with the program in
the nearest precision below the configuration's, ``fabric.precision=bf16-true``
(parameters stored in bf16), held to the f32 reference by the driver's own
``compare`` and ``judge``.  It has to come out as not correct: the run ends
before its window with ``"correct": false`` and exit code 1, and the earlier
line ``compare_with_reference`` holds the readings.

Run as the cell itself, on the chip:
``python benchmarks/sdar_bf16_reading.py --workload sdar_ep8_train --seed <n> --seconds 4``.
It names no cell: ``--workload joyai_ep_train`` gives the same second reading for
``chipbench/drivers/causal_lm_train.py`` (PR 30)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

LOWER = "bf16-true"


def main():
    import run as bench_run  # chipbench/run.py
    from chipbench import harness

    load = harness.load_json

    def lowered(*parts):
        loaded = load(*parts)
        for key in ("overrides", "tiny_overrides"):  # the configuration's and the traffic mix's
            loaded[key] = [o for o in loaded.get(key, []) if not o.startswith("fabric.precision=")]
        if parts[0] == "configs":
            loaded["overrides"].append(f"fabric.precision={LOWER}")
            loaded["precision"] = LOWER
        return loaded

    harness.load_json = lowered
    return bench_run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
