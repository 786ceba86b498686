"""One benchmark run of a cell, and after it the program's own counters and gauges that no metric reads.

    python3 tools/cell_registry.py [--match REGEX] -- --workload olmoe-1b-7b-span4.decode32 --seed 7 --seconds 51

From the root of a checkout. Runs `perf.run` in this process (everything after `--` is its command line,
`--rehearse-cpu` included), prints its result line as ever, and then, on stderr, one line `[registry] {...}`:
every series of the process's telemetry registry whose metric's name matches `--match` (default: the decode
path's, `hivemind_moe_decode_`), as they stand when the run has ended. What it is for: a program counter that
says whether a mechanism engaged — `hivemind_moe_decode_padding_cache_bytes`,
`hivemind_moe_decode_cache_bytes_donated_total{path}`, `hivemind_moe_decode_session_evictions_total{reason}`,
`hivemind_moe_decode_programs_total{origin}` (the decode programs built, and those a block was handed by another of its kind) —
read inside a cell's real traffic without an edit to the benchmark. A tree without a metric leaves it out."""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.getcwd())


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--match", default="hivemind_moe_decode_", help="regular expression, searched in a metric's name")
    parser.add_argument("run", nargs=argparse.REMAINDER, help="`--`, then perf.run's own command line")
    args = parser.parse_args()
    import perf.run  # set-up is counted from this import, as under `python3 -m perf.run`

    code = perf.run.main(args.run[1:] if args.run[:1] == ["--"] else args.run)
    from hivemind_tpu.telemetry import REGISTRY

    match = re.compile(args.match)
    series = {name: entry.get("series") for name, entry in REGISTRY.snapshot().items() if match.search(name)}
    print("[registry] " + json.dumps(series, sort_keys=True), file=sys.stderr, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
