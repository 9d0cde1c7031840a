"""The control of ``correct``: the reference put in the program's place
one precision step below what the configuration states, at the cell's
own size, on several seeds (each builder module carries its own
``control``).  Every seed's control has to fail at least one limit; the
readings set the limits' upper end (``PERF.md`` keeps them).  Run on the
chip; never by the benchmark.

    python3 -m benchmark.control --config <name> --seeds 11,12,13
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.control")
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from benchmark import builders, compare, manifest, run

    config = manifest.config(manifest.load(), args.config)
    run.open_chip(config.get("env"))
    control = builders.load(config["builder"]).control
    seeds = [int(s) for s in args.seeds.split(",")]
    refused = 0
    for seed in seeds:
        numbers = control(config, seed)
        ok, compared = compare.verdict(
            numbers, {k: config["limits"][k] for k in numbers})
        refused += not ok
        print(json.dumps({"seed": seed, "control_correct": ok,
                          "compared": compared}), flush=True)
    print(f"control refused on {refused} seed(s) of {len(seeds)}",
          flush=True)
    return 0 if refused == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
