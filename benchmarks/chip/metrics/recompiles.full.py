"""Programs JAX built inside the measured window: the program's
``jax.compile`` spans (a fresh compile, or a load from the persistent
compile cache, which JAX times inside the compile) plus any
``jax.cache_get`` span outside one, that ended in the window. The
compiled function's name is in each span's ``fun``. 0 is the expected
reading; none when the program records no compile spans."""
import os


def read(run):
    from harness import load_module
    try:
        from repro.obs.spans import COMPILE_SPANS  # noqa: F401
    except ImportError:
        return None
    base = load_module(os.path.join(run.bench_dir, "metrics",
                                    "sampler_ms.sampled.py"),
                       "sampler_ms.sampled")
    from repro.obs import enabled
    if not enabled() or run._t_window is None or not run.window_s:
        return None
    compiles = base.window_spans(run, "jax.compile")
    loose = [g for g in base.window_spans(run, "jax.cache_get")
             if not any(c["tid"] == g["tid"]
                        and c["start_ns"] <= g["start_ns"]
                        and g["end_ns"] <= c["end_ns"] for c in compiles)]
    return float(len(compiles) + len(loose))
