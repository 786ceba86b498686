"""A profile of the server's event-loop thread inside one benchmark run of a serving cell.

    python3 tools/profile_loop.py --out chiprun_out/profile.txt -- --workload olmoe-1b-7b-span4.decode32 --seed 7 --seconds 51

Runs `perf.run` in this process and, from 12 s into the measured window (`--after`), looks at the one
thread every channel, mux and handler of the process shares (`utils/loop.get_loop_runner`), twice:

1. SAMPLED (`sys._current_frames` every millisecond, `--sample` seconds): the function on top of the
   thread's stack. A sampler runs when it gets the interpreter lock, so it sees the loop thread only
   where that thread let the lock go: inside calls that release it (the selector, socket calls, numpy)
   and where it was made to. Code that holds the lock (`os.cpu_count()`, say) is invisible to it. Read
   it as: where the loop thread stands while other threads run.
2. TIMED (`sys.setprofile` on that thread alone, `--count` seconds; cProfile is no use: since Python
   3.12 it records every thread of a process into one table): own time by function, Python and
   built-in, and calls a second and a frame (`SecureChannel._seal` + `_open` in the same seconds), with
   the built-ins that enter the kernel marked. A built-in's time is its own (clock reads at its two
   ends); a Python function's own time carries the callback's cost for each call it makes (printed:
   about a microsecond), and the whole thread runs slower meanwhile, so read microseconds a frame and
   ranks, not seconds a second. A thread that waits for the interpreter lock waits inside some
   function, which is charged.

`--watch a,b` names functions to follow whatever their rank: the share of the samples with the function
anywhere on the stack (what it HOLDS of the thread, its callees included), and under the timer its calls a
second and its microseconds a call, own and with its callees.

The run's result line is printed as ever, but a profiled run is not a measurement."""

from __future__ import annotations

import argparse
import collections
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ENTERS_KERNEL = {"send", "sendall", "sendto", "sendmsg", "recv", "recv_into", "recvfrom", "poll", "select", "cpu_count",
                 "sched_getaffinity", "getpid", "urandom", "read", "write", "stat", "fstat", "close", "accept", "connect"}


def _code_name(code) -> str:
    path = code.co_filename
    return f"{os.path.relpath(path) if path.startswith('/') else path}:{code.co_firstlineno}({code.co_name})"


def _where(frame) -> str:
    return _code_name(frame.f_code)


def _enters_kernel(key) -> bool:
    """`key`: a built-in as the timer names it, (module or None, qualified name)."""
    return key[1].rpartition(".")[2] in ENTERS_KERNEL or key[0] == "posix"


def _sample(thread_id: int, seconds: float, watch=()):
    own, lines, callers, taken = collections.Counter(), collections.Counter(), collections.Counter(), 0
    within = collections.Counter()  # a watched function anywhere on the stack: the samples it HOLDS, callees included
    until = time.monotonic() + seconds
    while time.monotonic() < until:
        time.sleep(0.001)
        frame = sys._current_frames().get(thread_id)
        if frame is None:
            continue
        back, here = frame.f_back, _where(frame)  # the frame is live: read each field once
        taken += 1
        own[here] += 1
        lines[f"{here} line {frame.f_lineno}"] += 1
        if back is not None:
            callers[f"{here} <- {_where(back)}"] += 1
        held = set()
        while frame is not None and watch:
            if frame.f_code.co_name in watch:
                held.add(frame.f_code.co_name)
            frame = frame.f_back
        within.update(held)
    return own, lines, callers, within, taken


class _Timer:
    """Own time by function on ONE thread: `sys.setprofile`'s events with a clock read each."""

    def __init__(self):
        self.own, self.calls = collections.Counter(), collections.Counter()  # key -> ns, key -> calls
        self.whole = collections.Counter()  # key -> ns from call to return, callees included
        self._stack = []
        clock, stack, own, calls, whole = time.perf_counter_ns, self._stack, self.own, self.calls, self.whole

        def on_event(frame, event, arg):
            now = clock()
            if event == "call":
                stack.append([frame.f_code, now, 0])
            elif event == "c_call":
                stack.append([(arg.__module__, arg.__qualname__), now, 0])
            elif stack:  # return, c_return, c_exception (a coroutine's suspension is a return, its resumption a call)
                key, began, children = stack.pop()
                elapsed = clock() - began
                own[key] += elapsed - children
                whole[key] += elapsed
                calls[key] += 1
                if stack:
                    stack[-1][2] += elapsed

        self.on_event = on_event

    @staticmethod
    def name(key) -> str:
        return f"{{{key[0] or 'method'}}} {key[1]}" if isinstance(key, tuple) else _code_name(key)

    def callback_ns(self) -> float:
        """What one Python call costs under this callback beyond its own body, measured on this thread."""
        def nothing():
            pass

        timer = _Timer()
        sys.setprofile(timer.on_event)
        for _ in range(20000):
            nothing()
        sys.setprofile(None)
        return timer.own[nothing.__code__] / max(timer.calls[nothing.__code__], 1)


def _count(runner, seconds: float):
    timer = _Timer()
    runner.call_soon(sys.setprofile, timer.on_event)
    time.sleep(seconds)
    runner.call_soon(sys.setprofile, None)
    time.sleep(0.2)
    return timer


def _profile(begin: float, out: str, sample_s: float, count_s: float, watch=()) -> None:
    from hivemind_tpu.telemetry import REGISTRY
    from hivemind_tpu.utils.loop import get_loop_runner

    runner = get_loop_runner()
    time.sleep(max(begin - time.monotonic(), 0.0))

    def steps() -> float:
        series = REGISTRY.snapshot().get("hivemind_moe_decode_steps_total", {}).get("series", {})
        return sum(value for value in series.values() if isinstance(value, (int, float)))

    began, steps_before = time.monotonic(), steps()
    own, lines, callers, within, taken = _sample(runner._thread.ident, sample_s, watch)
    sampled_s, steps_sampled = time.monotonic() - began, steps() - steps_before
    began, steps_before = time.monotonic(), steps()
    timer = _count(runner, count_s)
    counted_s, steps_counted = time.monotonic() - began, steps() - steps_before
    by_name = {code.co_name: timer.calls[code] for code in timer.calls if not isinstance(code, tuple)
               and code.co_name in ("_seal", "_open") and code.co_filename.endswith("crypto_channel.py")}
    frames = max(by_name.get("_seal", 0) + by_name.get("_open", 0), 1)

    with open(out, "w") as handle:
        def say(text=""):
            print(text, file=handle)

        say(f"loop thread {runner._thread.name}: {taken} samples in {sampled_s:.1f} s ({steps_sampled / sampled_s:.0f} block steps a second meanwhile)")
        for title, table, top in (("own time by function", own, 25), ("by line", lines, 25), ("by function and caller", callers, 25)):
            say(f"\n{title}: share of samples, seconds a second of this thread")
            for where, count in table.most_common(top):
                say(f"  {100 * count / taken:5.1f} %  {where}")
        if watch:
            say("\nwatched functions: share of samples with the function anywhere on the stack (its callees included)")
            for name in watch:
                say(f"  {100 * within[name] / max(taken, 1):5.1f} %  {name}")
        total_ns = sum(timer.own.values())
        say(f"\ntimed for {counted_s:.1f} s under sys.setprofile ({steps_counted / counted_s:.0f} block steps a second meanwhile); frames sealed "
            f"{by_name.get('_seal', 0)}, opened {by_name.get('_open', 0)}; own time accounted {total_ns / 1e9:.2f} s; a Python call costs "
            f"{timer.callback_ns():.0f} ns under the callback (measured on the profiler's thread, charged to the caller's own time)")
        say("own time by function, Python and built-in:  share of the accounted time | us a frame | calls a frame | us a call | K: enters the kernel")
        for key, ns in timer.own.most_common(45):
            name, calls = timer.name(key), timer.calls[key]
            mark = "K" if isinstance(key, tuple) and _enters_kernel(key) else " "
            say(f"  {100 * ns / total_ns:5.1f} %  {ns / frames / 1e3:7.2f}  {calls / frames:6.2f}  {ns / max(calls, 1) / 1e3:8.2f}  {mark} {name}")
        if watch:
            say("\nwatched functions, timed:  calls a second | us a call own | us a call with its callees | share of the accounted time with its callees")
            for key, calls in timer.calls.items():
                if not isinstance(key, tuple) and key.co_name in watch:
                    say(f"  {calls / counted_s:8.0f}  {timer.own[key] / calls / 1e3:8.2f}  {timer.whole[key] / calls / 1e3:8.2f}  "
                        f"{100 * timer.whole[key] / total_ns:5.1f} %  {timer.name(key)}")
        say("\nbuilt-ins that enter the kernel:  calls a second (a lower bound: the thread is slowed) | calls a frame | us a call")
        for key, calls in timer.calls.most_common():
            if isinstance(key, tuple) and _enters_kernel(key):
                say(f"  {calls / counted_s:8.0f}  {calls / frames:6.2f}  {timer.own[key] / calls / 1e3:8.2f}  {timer.name(key)}")
    print(f"profile written to {out}", file=sys.stderr, flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", required=True)
    parser.add_argument("--after", type=float, default=12.0, help="seconds into the window")
    parser.add_argument("--sample", type=float, default=20.0, help="seconds of sampling")
    parser.add_argument("--count", type=float, default=6.0, help="seconds of counting after them")
    parser.add_argument("--watch", default="", help="function names, comma-separated: each one's share of the samples and its time a call, callees included")
    args, rest = parser.parse_known_args()
    rest = [arg for arg in rest if arg != "--"]

    from perf import run
    from perf.runners import block_server

    go = block_server.LoadGenerators.go

    def go_and_profile(self, begin, end):
        threading.Thread(target=_profile, args=(begin + args.after, args.out, args.sample, args.count, tuple(filter(None, args.watch.split(",")))), name="profile-loop", daemon=True).start()
        return go(self, begin, end)

    block_server.LoadGenerators.go = go_and_profile
    return run.main(rest)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
