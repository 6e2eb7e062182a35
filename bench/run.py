"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload fem_mesh.serve --seed 7 --seconds 30 \
        --trace 0

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``.  ``--trace 0`` measures the cell's end-to-end metrics;
``--trace 1`` runs the same traffic with the program's spans and the
profiler on, and reports the cell's per-layer metrics, the device's busy
time and a breakdown of the device trace.  Either way every answer the
window produced is checked against the float64 reference, and the last
line of standard output is one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  Artifacts a restarted deployment would keep (the
graph store and the hierarchy) and JAX's compilation cache live under
``bench/.cache/`` in the checkout.
"""
from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

from benchkit import spec  # noqa: E402
from benchkit.result import emit  # noqa: E402

CACHE = os.path.join(HERE, ".cache")


def source_digest(root: str = ROOT) -> str:
    """Digest of every file of the program (``src/``): artifacts built by
    other code are never loaded."""
    h = hashlib.sha256()
    src = os.path.join(root, "src")
    for dirpath, dirnames, filenames in os.walk(src):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if name.endswith((".pyc", ".pyo")):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, src).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def configure_jax(cache_dir: str) -> None:
    """JAX's persistent compilation cache at one fixed path in the
    checkout, for every program whatever its compile time."""
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_device(chips: int):
    """``(device dict, None)`` on a TPU host with enough chips, else
    ``(None, reason)``."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return None, f"no TPU: JAX found {dev.platform!r}"
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devices)}"
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}, None


def measure(cell, seed: int, seconds: float, trace: bool, peaks: dict,
            cache: str = CACHE, t_start: float = T_START,
            service_kwargs=None):
    """Run ``cell`` once; returns ``(Run, metrics, breakdown)``."""
    from benchkit import job, serve
    from benchkit.compiles import CompileCounter

    counter = CompileCounter()
    drivers = {"open": serve.run, "closed": job.run}
    profile_dir = os.path.join(cache, "profile")
    shutil.rmtree(profile_dir, ignore_errors=True)
    run = drivers[cell.traffic["loop"]](
        cell, seed, seconds, trace, t_start,
        disk_dir=os.path.join(cache, source_digest(), "artifacts"),
        compiles=counter, service_kwargs=service_kwargs,
        profile_dir=profile_dir)
    shutil.rmtree(profile_dir, ignore_errors=True)
    run.notes.insert(0, f"compiles in the window {counter.compiles} "
                        f"(executables loaded from the cache "
                        f"{counter.loads})")
    metrics, breakdown = {}, None
    if trace:
        ctx = dict(run.ctx, peaks=peaks, traffic=cell.traffic,
                   config=cell.config)
        for m in cell.per_layer:
            value = spec.layer_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run.ctx.get("trace"):
            breakdown = run.ctx["trace"]["breakdown"]
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                  "unit": m["unit"]}
    return run, metrics, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    configure_jax(os.path.join(CACHE, "jax"))
    device, reason = find_device(cell.chips)
    if device is None:
        print(f"bench: {reason}; this benchmark runs only on a TPU",
              file=sys.stderr)
        return 2
    from benchkit.roofline import peaks as peaks_of

    peaks = peaks_of(device["kind"])
    run, metrics, breakdown = measure(cell, args.seed, args.seconds,
                                      bool(args.trace), peaks)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if args.trace:
        t = run.ctx.get("trace") or {}
        device["busy_s"] = t.get("busy_s")
        device["window_s"] = t.get("window_s")
    emit(run, metrics, device, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
