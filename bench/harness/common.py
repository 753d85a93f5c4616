"""Start-up, spans, device facts and the result line shared by every cell."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from contextlib import contextmanager

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# four faked CPU devices in a rehearsal, enough for any cell of the schema
REHEARSE_DEVICES = 4


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so set-up
    counts interpreter start and imports too."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])          # field 22 of stat(5)
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded by file path so that a
    name may hold dots (``serve_idle_share.steady``)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def start_jax(rehearse: bool, chips: int):
    """Import JAX held to the TPU (or, to rehearse, to the CPU with faked
    devices), with the compile cache at its fixed path.  Off the TPU a
    real run raises SystemExit before anything is measured."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={REHEARSE_DEVICES}")
    # the TPU library otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the benchmark's cache, for JAX and for any program code that reads
    # the variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # cache every program, so the second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}")
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        raise SystemExit(f"expected a {want} device, got {devs[0]}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} devices, have "
                         f"{len(devs)}")
    return jax


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def start_trace(jax, trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: it would time every
    Python call of a host-bound loop and slow what it measures.  Host
    spans (``span``) and the runtime's own host events stay."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
