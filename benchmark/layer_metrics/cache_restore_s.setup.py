"""Program build, compile cache: seconds in ``executor.cache_restore``
spans, summed: the entry's files read and checked (``read_ms``) and
``deserialize_and_load`` (``load_ms``); a miss is the few microseconds of
looking.  0.0 on a run that restored none; nothing where the program
recorded neither a restore nor a compile."""


def read(obs):
    from benchmark import setup_spans

    if not setup_spans.compiled_or_restored(obs):
        return None
    return setup_spans.seconds(obs, "executor.cache_restore") or 0.0
