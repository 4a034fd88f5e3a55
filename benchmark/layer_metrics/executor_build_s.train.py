"""Program build, compile cache: seconds a training run's cache-miss steps
spend before any executable is asked for: ``executor.build`` (the IR passes,
``check_before_compile``, ``build_block_fn``) and ``executor.disk_key``
(``program_fingerprint`` over ``Program.to_dict()``, ``code_fingerprint``,
``artifact_key``), the start-up program's and the step's summed.  Host
work that a restored run pays like a cold one."""


def read(obs):
    if obs.get("kind") != "train":
        return None
    from benchmark import setup_spans

    return setup_spans.seconds(obs, "executor.build", "executor.disk_key")
