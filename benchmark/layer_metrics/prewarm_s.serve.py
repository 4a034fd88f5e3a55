"""Step function: seconds in ``serving.prewarm``: one ``executor.warmup``
a lane bucket, each a disk key, a restore or a compile."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    from benchmark import setup_spans

    return setup_spans.seconds(obs, "serving.prewarm")
