"""Runs the program's sidecar (`hotstuff_tpu.crypto.remote.main`) unchanged in
this process, and beside it a small thread that answers the harness through
files in `--control-dir`. The harness parent never imports JAX, and only the
process that holds the chip can trace it or read its memory:

  trace.start  ->  jax.profiler trace of `--trace-seconds` into `--trace-dir`,
                   then `trace.done` with the traced stretch's length
  device.ask   ->  `device.json`: platform, kind, count, peak bytes in use

    python -m chipbench.sidecar_shim --control-dir D [--trace-dir T]
        [--fault skip_half] -- --port 8900 --backend tpu ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def _write(path: str, obj) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def _device() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def _trace(trace_dir: str, trace_s: float) -> dict:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.time()
    time.sleep(trace_s)
    t1 = time.time()
    jax.profiler.stop_trace()
    return {"window_s": t1 - t0, "t0": t0, "t1": t1, "written_s": time.time() - t1}


def _serve_control(control: str, trace_dir: str | None, trace_s: float) -> None:
    traced = False
    while True:
        time.sleep(0.05)
        if trace_dir and not traced and os.path.exists(os.path.join(control, "trace.start")):
            traced = True
            try:
                reply = _trace(trace_dir, trace_s)
            except Exception as e:  # the harness has to hear of it
                reply = {"error": repr(e)}
            _write(os.path.join(control, "trace.done"), reply)
        if os.path.exists(os.path.join(control, "device.ask")) and not os.path.exists(
            os.path.join(control, "device.json")
        ):
            _write(os.path.join(control, "device.json"), _device())


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    split = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--control-dir", required=True)
    ap.add_argument("--trace-dir")
    ap.add_argument("--trace-seconds", type=float, default=0.4)
    ap.add_argument("--fault")
    args = ap.parse_args(argv[:split])
    if args.fault:
        from . import faulty

        faulty.break_sidecar(args.fault)
    threading.Thread(
        target=_serve_control,
        args=(args.control_dir, args.trace_dir, args.trace_seconds),
        daemon=True,
    ).start()
    from hotstuff_tpu.crypto import remote

    remote.main(argv[split + 1 :])


if __name__ == "__main__":
    main()
