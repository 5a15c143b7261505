"""What every builder shares: the set-up clock, earlier-line printing,
the device's description, the compile counter and the traced tail."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from typing import List, Optional, Tuple

from . import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: everything a run writes (traces; the compile cache is the program's
#: own ``<checkout>/.jax_cache``) goes here, inside the checkout, at a
#: fixed path, listed in .gitignore
WORK_DIR = os.path.join(ROOT, ".bench_work")


def say(msg: str):
    """An earlier line of standard output (never the last)."""
    print(msg, flush=True)


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record, so
    that interpreter start-up and imports count as set-up."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _IMPORTED_AT


_IMPORTED_AT = time.monotonic()


class Phases:
    """How set-up divides.  ``mark(name)`` closes the phase that began at
    the previous mark; the first phase began with the process."""

    def __init__(self):
        self._t = time.monotonic()
        self.rows: List[Tuple[str, float]] = [
            ("interpreter start and harness imports", process_age_s())]

    def mark(self, name: str):
        now = time.monotonic()
        self.rows.append((name, now - self._t))
        self._t = now

    def add(self, name: str, seconds: float):
        """A phase timed elsewhere (the ramp: a fixed sleep)."""
        self.rows.append((name, seconds))
        self._t = time.monotonic()

    def report(self):
        say("set-up breakdown (s): " + json.dumps(
            {k: round(v, 3) for k, v in self.rows}))


def compile_count() -> int:
    """Fresh traces the executor has made (each is a compile or a load
    from the persistent cache) — the program's own counter."""
    from paddle_tpu.monitor import stat
    return int(stat("executor_compile_count").get())


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "used": chips}


def memory_now(chips: int) -> List[int]:
    """Bytes taken on each chip used, now: the arrays the allocator holds
    plus the scratch a running executable has reserved, both from ONE
    ``memory_stats()`` call, so the sum is a state the chip was in."""
    import jax
    out = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("bytes_in_use", 0))
                   + int(stats.get("bytes_reserved", 0)))
    return out


def memory_peak_bytes(chips: int, samples: List[int]) -> Optional[int]:
    """Peak bytes on the fullest of the chips used.  The allocator's
    ``peak_bytes_in_use`` counts arrays only: the scratch of a running
    executable (a training step's activations) is under
    ``bytes_reserved`` (my chip runs, PR 24).  So the peak is the larger
    of the arrays' own peak and the largest ``memory_now`` sample the
    builder took inside the window; neither can exceed the true peak."""
    import jax
    peaks = []
    for d, sample in zip(jax.devices()[:chips], samples):
        stats = d.memory_stats() or {}
        say(f"memory_stats {d}: " + json.dumps(
            {k: int(v) for k, v in stats.items()})
            + f"; largest in-window sample of bytes_in_use + "
              f"bytes_reserved: {sample}")
        if "peak_bytes_in_use" in stats:
            peaks.append(max(int(stats["peak_bytes_in_use"]), sample))
    return max(peaks) if peaks else None


class Tracer:
    """The traced tail: ``start()`` / ``stop()`` around a few seconds of
    the same load, after the measured window.  ``stop()`` reduces the
    trace and returns the summary (None when tracing is off)."""

    def __init__(self, enabled: bool, cell: str, chips: int):
        self.enabled = enabled
        self.chips = chips
        self.dir = os.path.join(WORK_DIR, "trace", cell)
        self.t_start = self.t_stop = None

    def start(self):
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        kwargs = {}
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the python tracer slows
            opts.host_tracer_level = 2       # the host loop it measures
            kwargs["profiler_options"] = opts
        except AttributeError:
            pass
        jax.profiler.start_trace(self.dir, **kwargs)
        self.t_start = time.monotonic()

    def stop(self) -> Optional[dict]:
        if not self.enabled:
            return None
        import jax
        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        say(f"trace: {path} ({os.path.getsize(path)} bytes), host window "
            f"{self.t_stop - self.t_start:.3f} s")
        for row in trace_reduce.describe_xplane(path)[:20]:
            say("trace line: " + row)
        trace = trace_reduce.load_xplane(path)
        summary = trace_reduce.summarize(trace, self.chips)
        say("trace summary: " + json.dumps(summary))
        shutil.rmtree(self.dir, ignore_errors=True)
        return summary


def annotate(name: str):
    """A host span of the benchmark's own in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_SPAN_PREFIX + name)


def finish(line: dict, code: int = 0, hard: bool = False):
    """Print the last line and leave.  ``hard`` skips interpreter
    shutdown: a decode engine whose worker is still draining requests
    nobody waits for would otherwise hold the exit for their length."""
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    sys.stderr.flush()
    if hard:
        os._exit(code)
    sys.exit(code)
