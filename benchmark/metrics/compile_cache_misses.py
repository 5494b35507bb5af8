"""Programs of set-up that JAX's persistent cache was asked for and did not
have, so that they were compiled: entries of the program's compile log
(`paddle_tpu.core.compile_cache.compile_log()`) that are no hit and started
before the traced slice (the reference's programs, after the window, are not
the program's). 0 when the cache is warm. Layer: compile cache. Moves
setup_s."""


def read(run):
    try:
        from paddle_tpu.core.compile_cache import compile_log
    except ImportError:
        return None         # a program without a compile log
    log, t0 = compile_log(), run.get("trace_t0")
    if not log or t0 is None:
        return None
    return sum(e["cache"] in ("miss", "unstored") for e in log if e["t0"] < t0)
