"""Read a cell's control on the chip: the reference put in the program's
place and computed one precision below the configuration's.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 \
        [--variant bfloat16|bfloat16_pairs]

prints, for each seed, every number the cell's check compares with the
control's reading beside the limit; a control has to read over a limit.
``bfloat16`` (the default) holds positions, strengths and the pair
arithmetic in bfloat16; ``bfloat16_pairs`` only the pair arithmetic, after
float32 differences.
The benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    ap.add_argument("--variant", default="bfloat16",
                    choices=("bfloat16", "bfloat16_pairs"))
    args = ap.parse_args(argv)
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness

    bm = harness.load_benchmark()
    cell = harness.find(bm["workloads"], args.workload, "workload")
    cfg = harness.load_json(harness.find(bm["configs"], cell["config"],
                                         "config")["file"])
    traffic = harness.load_json(harness.traffic_file(cell["traffic"]))
    driver = harness.load_module(harness.driver_file(traffic["driver"]),
                                 "driver")
    dev = jax.devices()[0]
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        readings = driver.control(cfg, traffic, seed, args.variant)
        limit = float(cfg["check"]["rel_l2_limit"])
        over = {k: not v <= limit for k, v in readings.items()}
        failed_all &= any(over.values())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variant": args.variant,
                          "control": readings, "limit": limit,
                          "control_fails": any(over.values()),
                          "device": dev.device_kind}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
