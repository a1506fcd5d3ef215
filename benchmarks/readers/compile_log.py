"""JAX's compile log (``jax_log_compiles``). ``args.what``:
``setup_seconds`` (compile or cache-load seconds before the window opened),
``setup_hits`` (persistent-cache hits before it), ``window_count``
(programs compiled between its opening and its close: must read 0)."""


def read(record, args, ctx):
    log, t_open = record["compiles"], record["t_open"]
    what = args["what"]
    if what == "setup_seconds":
        return sum(sec for t, _name, sec in log.compiled if t < t_open)
    if what == "setup_hits":
        return len([h for h in log.hits if h[0] < t_open])
    if what == "window_count":
        return len(log.between(t_open, record["t_close"]))
    raise ValueError(f"compile_log: unknown 'what' {what!r}")
