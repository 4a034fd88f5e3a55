"""Install sanity check (reference python/paddle/fluid/install_check.py:45
run_check — builds a tiny fc model, runs one train step, prints success)."""

import numpy as np

__all__ = ["run_check"]


def run_check():
    import paddle_tpu as fluid

    prog = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data("install_check_x", shape=[2])
        linear = fluid.layers.fc(x, 2)
        loss = fluid.layers.mean(linear)
        fluid.optimizer.SGD(0.01).minimize(loss)
    feed = {"install_check_x": np.ones((2, 2), "float32")}

    # TPUPlace raises when JAX finds no accelerator (framework.py): a
    # broken chip path is a failed check, not a CPU pass
    import jax

    place = (fluid.CPUPlace() if jax.default_backend() == "cpu"
             else fluid.TPUPlace(0))
    exe = fluid.Executor(place)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[loss])
    dev = place.jax_device()
    print("Your paddle_tpu works well on %s (%s)."
          % (dev.platform.upper(), dev.device_kind))
    print("Your paddle_tpu is installed successfully! Let's start deep "
          "learning with paddle_tpu now.")
