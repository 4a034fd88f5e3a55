"""Step function: executables this process compiled itself
(``executor.compile`` spans whose ``source`` is ``compiled``; a fallback to
the lazy jit is not one).  0 on a restored run: it says of every traced run
whether the process that served or trained had compiled in it.  Nothing
where the program recorded neither a restore nor a compile."""


def read(obs):
    from benchmark import setup_spans

    if not setup_spans.compiled_or_restored(obs):
        return None
    return sum(s.get("attrs", {}).get("source") == "compiled"
               for s in setup_spans.spans(obs)["executor.compile"])
