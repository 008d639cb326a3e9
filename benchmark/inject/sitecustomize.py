"""The benchmark's hook inside the process that holds the chip.

Under `[topo] runtime = "process"` only the verify tile's child may
initialise a JAX backend, and the program has no profiler call and no
way to tell its parent what device it runs on.  `run.py` puts this
directory on PYTHONPATH before the topology starts; every spawned tile
child then imports this file at interpreter start.  It does nothing
unless FDT_BENCHMARK_HOOK_DIR is set.  When it is, a daemon thread
waits (during the boot) until THIS process has initialised a backend —
only the verify child ever does — writes `device.<pid>.json`, and then
BLOCKS on the directory's `cmd` pipe: it polls nothing and costs
nothing while the window runs.  One word a line comes down the pipe:

    start   -> jax.profiler.start_trace(<dir>/trace)   (traced runs only)
    stop    -> jax.profiler.stop_trace(), then `stopped.<pid>.json`
    stats   -> stats.<pid>.json: each local device's memory_stats();
               the last word of a run: the thread ends
"""

import os

_DIR = os.environ.get("FDT_BENCHMARK_HOOK_DIR")


def _write(name, obj):
    import json

    tmp = os.path.join(_DIR, f".{name}.tmp")
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, os.path.join(_DIR, name))


def _serve():
    import sys
    import time

    # Never import here: a second thread importing jax while the tile's
    # main thread is half-way through the same import breaks it.  Look
    # only at modules that are already fully imported.
    while True:
        jax_mod = sys.modules.get("jax")
        xb = sys.modules.get("jax._src.xla_bridge")
        if (jax_mod is not None and xb is not None
                and not getattr(jax_mod.__spec__, "_initializing", False)
                and xb.backends_are_initialized()):
            break
        time.sleep(0.25)
    import jax

    pid = os.getpid()
    devs = jax.local_devices()
    _write(f"device.{pid}.json", {
        "pid": pid, "platform": devs[0].platform,
        "kind": devs[0].device_kind, "count": len(devs)})
    t_start = 0
    with open(os.path.join(_DIR, "cmd")) as pipe:  # blocks until run.py opens it
        for word in pipe:  # blocks until run.py writes; EOF: run.py is gone
            word = word.strip()
            if word == "start":
                # device ops and the runtime's own host spans; no Python
                # tracer (it would slow the very host path under test)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(_DIR, "trace"),
                                         profiler_options=opts)
                t_start = time.monotonic_ns()
            elif word == "stop":
                t_stop = time.monotonic_ns()
                jax.profiler.stop_trace()
                _write(f"stopped.{pid}.json",
                       {"start_ns": t_start, "stop_ns": t_stop})
            elif word == "stats":
                _write(f"stats.{pid}.json",
                       [d.memory_stats() or {} for d in devs])
                return


if _DIR:
    import threading

    threading.Thread(target=_serve, daemon=True,
                     name="fdt-benchmark-hook").start()
