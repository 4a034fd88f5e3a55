"""Model + cache: the share of a step's (token, output) assignments that
fall on identity experts, which return their input and compute nothing: 100
x ``moe_zero_assignments`` / (``moe_local_assignments`` +
``moe_absent_assignments`` + ``moe_zero_assignments``) of the window's
``serving.decode_step`` spans (means over the layers that route), the median
over its steps.  Under a router of 512 experts and 256 identity experts whose
selection bias is balanced over all 768 outputs, as training balances it, it
reads 33.3: a token then computes 8 real experts of its 12 in the mean.  Well
under says the bias starves the identity experts (every token pays for 12);
well over, that the model computes less than it was trained to.  It is a
reading to check, not one to push: declared ``higher`` because more identity
assignments are fewer experts streamed, but what is right is a third.  Reads
nothing where the spans carry no such attributes (a router as wide as its
experts, the parent of the PR that added them)."""

import statistics

NEEDS = ("moe_zero_assignments", "moe_local_assignments",
         "moe_absent_assignments")


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = []
    for span in obs.get("decode_spans") or []:
        a = span.get("attrs", {})
        if any(key not in a for key in NEEDS):
            continue
        total = sum(a[key] for key in NEEDS)
        if total:
            got.append(100.0 * a["moe_zero_assignments"] / total)
    return statistics.median(got) if got else None
