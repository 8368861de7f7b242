"""What each window program costs the set-up: run the benchmark's command
with every call ``GenerationEngine.warmup()`` makes of a window program timed
to its result, and print one ``warmup.program`` line a call (first calls are
builds: trace, lower, load or compile; later calls of a program are its run
time alone, unless a token-feed form turned out a signature of its own) and
the ``pt.serve.warmup_program`` spans the engine itself recorded, where it
records them.

    python3 tools/warmup_spans.py --workload <cell> --seed <n> --seconds <s>

``--warmup-only`` ends the process when ``warmup()`` returns, ``--profile``
puts each program's first call under ``cProfile``; everything else after the
script's name goes to ``benchmark/run.py`` as it is. The timing wraps
``_run_window`` from outside, so the same tool reads a checkout that has no
such span (copy it there). Beside each call's wall time: this thread's CPU
time (a build that waits reads lower), ``warmup.jax`` lines with what jax
says a build's steps took (trace, jaxpr -> MLIR, the compile cache's lookup
and load), and once a ``warmup.calib`` line, a fixed Python loop, for the
host's own speed in this process. It was this tool that found a build to be
tracing, a kernel's trace to be 0.7-1.4 s, and lines added to a traced
``step`` to slow every program's trace (PERF.md section 6, PR 36).
"""
import json
import os
import runpy
import sys
import time

T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PROFILE = "--profile" in sys.argv
if PROFILE:
    sys.argv.remove("--profile")


def main() -> None:
    from paddle_tpu.serving.generation import GenerationEngine

    warmup, run_window = GenerationEngine.warmup, GenerationEngine._run_window

    def timed_warmup(self):
        import jax

        seen = set()

        def timed(rows, W, *args, prefill=False, **kw):
            key = (rows, W, prefill)
            prof = None
            if PROFILE and key not in seen:
                import cProfile

                prof = cProfile.Profile()
            if not seen:
                # the host's own speed in this process, before any build: a
                # fixed loop on this thread's clock and on the wall's
                import threading

                w0, c0 = time.perf_counter(), time.thread_time()
                x = 0
                for i in range(3_000_000):
                    x += i & 3
                print("warmup.calib " + json.dumps(
                    {"loop_wall_s": round(time.perf_counter() - w0, 3),
                     "loop_cpu_s": round(time.thread_time() - c0, 3),
                     "threads": len(threading.enumerate()),
                     "load": os.getloadavg()[0],
                     "cores": len(os.sched_getaffinity(0))}), flush=True)
            t, c_thread, c_proc = (time.perf_counter(), time.thread_time(),
                                   time.process_time())
            if prof is not None:
                prof.enable()
            out = run_window(self, rows, W, *args, prefill=prefill, **kw)
            if prof is not None:
                prof.disable()
            jax.block_until_ready(out[0])
            print("warmup.program " + json.dumps(
                {"label": f"{'prefill' if prefill else 'window'}{W}",
                 "rows": rows, "first": key not in seen,
                 "s": round(time.perf_counter() - t, 3),
                 "thread_cpu_s": round(time.thread_time() - c_thread, 3),
                 "process_cpu_s": round(time.process_time() - c_proc, 3),
                 }), flush=True)
            if prof is not None:
                import io
                import pstats

                buf = io.StringIO()
                pstats.Stats(prof, stream=buf).sort_stats("tottime") \
                    .print_stats(22)
                print("warmup.profile " + buf.getvalue().replace(
                    "\n", "\nwarmup.profile "), flush=True)
            seen.add(key)
            return out

        self._run_window = timed
        t = time.perf_counter()
        try:
            return warmup(self)
        finally:
            del self._run_window
            from paddle_tpu.observability.trace.request_trace import tracer

            spans = [{"label": r["args"].get("label"),
                      "rows": r["args"].get("rows"),
                      "s": round(r["dur_us"] / 1e6, 3)}
                     for r in tracer().worker_spans()
                     if r["name"] == "pt.serve.warmup_program"]
            print("warmup.total " + json.dumps(
                {"s": round(time.perf_counter() - t, 3), "spans": spans}),
                flush=True)

    # where jax itself says a build's time goes (trace, jaxpr -> MLIR, the
    # compile-cache lookup and the executable's load); events under 50 ms
    # are summed into the next line's "small_s"
    import jax

    small = [0.0]

    def on_duration(event, secs, **_kw):
        if secs < 0.05:
            small[0] += secs
            return
        print("warmup.jax " + json.dumps(
            {"event": event.rsplit("/", 1)[-1], "s": round(secs, 3),
             "small_s": round(small[0], 3)}), flush=True)
        small[0] = 0.0

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    only = "--warmup-only" in sys.argv
    if only:
        sys.argv.remove("--warmup-only")
        inner = timed_warmup

        def timed_warmup(self):  # noqa: F811
            inner(self)
            print("warmup.setup_s " + json.dumps(
                {"s": round(time.time() - T0, 3)}), flush=True)
            os._exit(0)

    GenerationEngine.warmup = timed_warmup
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + sys.argv[1:]
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
