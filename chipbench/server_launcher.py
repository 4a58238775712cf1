#!/usr/bin/env python3
"""The benchmark's launcher of the system under test: the one process
on the chip.

    python3 chipbench/server_launcher.py --workdir DIR [--profile] -- start ...

Runs `tigerbeetle_tpu.main.main([...])` in this process, with nothing
changed, and adds the two things only the process that holds the chip
can give the benchmark:

- after the server has stopped, one line
  `{"chipbench_device": {"memory_peak_bytes": ...}}` from the device's
  own `memory_stats()`;
- a count of XLA compiles at the instants the harness marks by
  creating DIR/mark.<name> (window begin and end), so that compiles
  inside the measured window are a number of their own;
- with --profile, a jax.profiler trace that starts when the harness
  creates DIR/profile.go and stops when it creates DIR/profile.stop
  (files, not new options of the program): the harness brackets the
  whole measured window with them, so the trace holds every checkpoint
  of the window and no luck decides which. DIR/profile.started appears
  once the trace runs; DIR/profile.json says when it ran; a
  `chipbench_anchor` annotation inside the trace, entered at a recorded
  wall-clock instant, puts the trace's clock and the program's span
  clock (wall-anchored) on one axis.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def profile_when_asked(workdir: str, stop: threading.Event,
                       out: dict) -> None:
    go = os.path.join(workdir, "profile.go")
    halt = os.path.join(workdir, "profile.stop")
    while not os.path.exists(go):
        if stop.wait(0.02):
            return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the device and TraceMe only
    opts.host_tracer_level = 1
    t0 = time.time_ns()
    jax.profiler.start_trace(os.path.join(workdir, "profile"),
                             profiler_options=opts)
    out["start_call_wall_ns"] = t0
    out["started_wall_ns"] = time.time_ns()
    out["anchor_wall_ns"] = time.time_ns()
    with jax.profiler.TraceAnnotation("chipbench_anchor"):
        time.sleep(0.001)
    open(os.path.join(workdir, "profile.started"), "w").close()
    while not os.path.exists(halt) and not stop.wait(0.02):
        pass
    out["stop_call_wall_ns"] = time.time_ns()
    jax.profiler.stop_trace()
    out["stopped_wall_ns"] = time.time_ns()
    with open(os.path.join(workdir, "profile.json.tmp"), "w") as f:
        json.dump(out, f)
    os.replace(os.path.join(workdir, "profile.json.tmp"),
               os.path.join(workdir, "profile.json"))


class Marks:
    """Compile counts (jax.monitoring) at the instants the harness
    marks with a file."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, workdir: str):
        self.workdir = workdir
        self.count = 0
        self.seconds = 0.0
        self.marks: dict = {}

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += seconds

    def watch(self, stop: threading.Event) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        while True:
            for name in os.listdir(self.workdir):
                if name.startswith("mark.") and name[5:] not in self.marks:
                    self.marks[name[5:]] = {
                        "compiles": self.count,
                        "compile_seconds": round(self.seconds, 3),
                        "wall_ns": time.time_ns()}
            if stop.wait(0.02):
                return


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workdir", required=True)
    p.add_argument("--profile", action="store_true")
    p.add_argument("program_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    program_args = [a for a in args.program_args if a != "--"]
    sys.path.insert(0, ROOT)
    from tigerbeetle_tpu.main import main as program_main

    stop = threading.Event()
    profile: dict = {}
    watcher = None
    marks = Marks(args.workdir)
    marker = threading.Thread(target=marks.watch, args=(stop,), daemon=True)
    marker.start()
    if args.profile:
        watcher = threading.Thread(
            target=profile_when_asked, args=(args.workdir, stop, profile),
            daemon=True)
        watcher.start()
    try:
        rc = program_main(program_args)
    finally:
        stop.set()
        marker.join(timeout=10)
        if watcher is not None:
            watcher.join(timeout=120)
    peak = None
    if "jax" in sys.modules:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [x for x in peaks if x is not None]
        peak = max(peaks) if peaks else None
    print(json.dumps({"chipbench_device": {"memory_peak_bytes": peak,
                                           "marks": marks.marks}}),
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
