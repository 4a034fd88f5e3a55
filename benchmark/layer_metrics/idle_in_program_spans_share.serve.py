"""Device: of the profiled window's idle time (the breakdown's
``idle_gaps`` less the device's own hand-over between operations), the
share in gaps whose label is one of the program's host phases
(``serving.*``, ``executor.*``): how much of the idle time the program
can name.  A health metric of the measurement, not of the program."""

PROGRAM_PREFIXES = ("serving.", "executor.")
DEVICE_OWN = "op_to_op_under_20us"


def read(obs):
    prof = obs.get("profile")
    if obs.get("kind") != "serve" or not prof:
        return None
    gaps = [(label, s) for label, s in prof["idle_gaps"]
            if label != DEVICE_OWN]
    total = sum(s for _label, s in gaps)
    if not total:
        return None
    named = sum(s for label, s in gaps if label.startswith(PROGRAM_PREFIXES))
    return 100.0 * named / total
