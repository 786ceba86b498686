"""One load-generator process, pinned to the CPU: the server's process owns the chip.

    python3 -m perf.loadgen --spec <file.json>

The spec (written by the runner) names the traffic generator, the server's
addresses, this process's share of the schedule and a result file. The process
connects with a DHT identity of its own, prints READY, waits for one line
`GO <begin> <end>` (time.monotonic, which one machine's processes share) on its
standard input, runs one thread per slot from `lead_seconds` before `begin` until
`end`, writes its result and exits."""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--spec", required=True)
    args = parser.parse_args()
    with open(args.spec) as handle:
        spec = json.load(handle)
    assert os.environ.get("JAX_PLATFORMS") == "cpu", "a load generator must not hold an accelerator"

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from perf.manifest import plugin

    generator = plugin("traffic", spec["generator"])
    dht = DHT(initial_peers=spec["initial_peers"], start=True)
    try:
        pipe = RemoteSequential(dht, spec["uid_prefix"], spec["num_blocks"])
        give_up = time.monotonic() + 60.0
        while True:  # the blocks are declared before we start; resolve them before READY
            try:
                pipe.decode_capacity()  # asks every block for its info: all are resolved
                break
            except Exception:
                if time.monotonic() > give_up:
                    raise
                time.sleep(0.5)
        print("READY", flush=True)
        line = sys.stdin.readline().split()
        if len(line) != 3 or line[0] != "GO":
            return 1
        begin, end = float(line[1]), float(line[2])
        results = [generator.new_result() for _ in spec["slots"]]
        lead = float(spec.get("lead_seconds", 0.0))
        threads = [
            threading.Thread(
                target=generator.drive_slot, daemon=True,
                args=(pipe, plan, dict(hidden=spec["hidden"], tag=f"{spec['tag']}s{i}", begin=begin, end=end,
                                       slot=spec["first_slot"] + i, slots=spec["slots_total"]), results[i]),
            )
            for i, plan in enumerate(spec["slots"])
        ]
        time.sleep(max(begin - lead - time.monotonic(), 0.0))
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=end - time.monotonic() + spec["drain_seconds"])
        stuck = sum(thread.is_alive() for thread in threads)
        with open(spec["result"] + ".tmp", "w") as handle:
            json.dump({"slots": results, "stuck_slots": stuck}, handle)
        os.replace(spec["result"] + ".tmp", spec["result"])
    finally:
        dht.shutdown()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
