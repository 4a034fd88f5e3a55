"""Program build, compile cache: seconds of ``import paddle_tpu``, first
line to last (span ``setup.import``, timed by the package itself, before any
flag can be on).  ``run.py`` imports ``jax`` and asks for the devices first,
so the runtime's start is not in it.  A program without the span gives
nothing."""


def read(obs):
    from benchmark import setup_spans

    return setup_spans.seconds(obs, "setup.import")
