"""Program build, compile cache: seconds in ``executor.compile`` spans,
summed: trace and lower (``lower_ms``), XLA's compile (``backend_ms``),
serialising and the store (``store_ms``).  0.0 on a restored run; nothing
where the program recorded neither a restore nor a compile."""


def read(obs):
    from benchmark import setup_spans

    if not setup_spans.compiled_or_restored(obs):
        return None
    return setup_spans.seconds(obs, "executor.compile") or 0.0
