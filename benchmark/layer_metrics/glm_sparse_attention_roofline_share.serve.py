"""Model + cache: the share of its roofline that attention over the chosen
rows reaches.  The least time it could take is the LARGER of the rows'
values and the lanes' queries and outputs
(``glm_cost.selected_floor_bytes_per_step`` of the median
``latent_rows_selected`` and ``lanes`` of the window's
``serving.decode_step`` spans: 1,152 B a row, not the 1,280 its pool holds it
in) at ``peaks.hbm_bytes_per_s`` and the absorbed form's operations over
those rows (``glm_cost.selected_flops_per_step``) at
``peaks.bf16_flops_per_s`` (64 heads over a row: 121 operations a byte,
half the chip's ridge).  The time is everything the selected read runs on
the device that the profile can name: the latent kernel over the gathered
rows (``latent_attention*``), the exact choice (``sort*`` / ``top_k*`` /
``TopK*`` operations) and the rows' gather (``gather*`` operations), a step:
work moved from the kernel into a gather or a sort stays in the denominator,
so such a move cannot read over 100.

The profile is reduced by operation name and not by ``named_scope``
(``benchmark/trace_reduce.py``): a gather or a sort that XLA folds into a
fusion under a plain ``fusion.N`` is not in the time (PERF.md section 7 says
which file would have to keep the scope); the kernel alone reads every byte
of the numerator, so the share stays under 100 either way.

Numerator and denominator are the window's median and the steps profiled
just after it, as the other long cells' shares: past ``index_topk`` a lane
reads 2,048 rows whatever its context, so the two agree more closely here
than where a step follows the contexts' sum.

Reads nothing where no latent kernel ran, without the spans' attributes,
without a device profile, or for a configuration without the keys
``glm_cost`` reads."""

import statistics

KERNEL = "latent_attention"
BESIDE = ("sort", "top_k", "topk", "TopK", "gather")


def read(obs):
    from benchmark import glm_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    config = obs.get("config") or {}
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps") \
            or any(key not in config for key in glm_cost.KEYS):
        return None
    ops = {name.lstrip("%_"): s
           for name, s in prof.get("op_seconds", {}).items()}
    kernel_s = sum(s for name, s in ops.items() if name.startswith(KERNEL))
    attrs = [a for a in (s.get("attrs", {})
                         for s in obs.get("decode_spans") or [])
             if "latent_rows_selected" in a and a.get("lanes")]
    if not kernel_s or not attrs:
        return None
    beside_s = sum(s for name, s in ops.items() if name.startswith(BESIDE))
    median = lambda key: statistics.median(a[key] for a in attrs)
    rows, lanes = median("latent_rows_selected"), median("lanes")
    floor_s = max(
        glm_cost.selected_floor_bytes_per_step(config, rows, lanes)
        / peaks["hbm_bytes_per_s"],
        glm_cost.selected_flops_per_step(config, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / ((kernel_s + beside_s) / obs["traced_steps"])
