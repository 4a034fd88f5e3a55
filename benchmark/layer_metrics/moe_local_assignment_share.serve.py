"""Model + cache: the share of a step's (token, expert) assignments that
fall on the experts this chip holds: 100 x ``moe_local_assignments`` /
(``moe_local_assignments`` + ``moe_absent_assignments``) of the window's
``serving.decode_step`` spans (means over the layers that route), the median
over its steps.  Holding 16 of 128 experts under a router that is whole and
even, it reads 12.5: well under says the router no longer scores all 128 or
the selection bias starves the held experts; 100 says nothing is held back
for the absent ones.  It is a reading to check, not one to push: declared
``lower`` because fewer assignments here are fewer experts streamed and a
shorter step, but what is right is 12.5.  Reads nothing where the spans carry no such attributes
(a model that holds every expert, the parent of the PR that added them)."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = []
    for span in obs.get("decode_spans") or []:
        a = span.get("attrs", {})
        if "moe_local_assignments" not in a \
                or "moe_absent_assignments" not in a:
            continue
        total = a["moe_local_assignments"] + a["moe_absent_assignments"]
        if total:
            got.append(100.0 * a["moe_local_assignments"] / total)
    return statistics.median(got) if got else None
