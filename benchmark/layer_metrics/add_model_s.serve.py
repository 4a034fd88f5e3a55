"""Model + cache: seconds in ``serving.add_model``: the weights laid out
as a step holds them (``serving.lay_out``), the pools' allocation
(``serving.cache_alloc``), the step's account and its functions."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    from benchmark import setup_spans

    return setup_spans.seconds(obs, "serving.add_model")
