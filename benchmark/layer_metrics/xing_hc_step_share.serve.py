"""Model + cache: the share of a Xing4.0 decode step's device time spent
mixing its four residual streams.  The time is the device trace's: the self
time of every operation whose ``jax.named_scope`` path lies under
``layer<i>/hc/`` (a sublayer's ``_maps``, ``_read``, ``_merge``) or under
``hc/start`` and ``hc/sum``, as a share of all the operations' self time
(``xing_cost.scoped_share``: the rule ``trace_reduce`` sums ``op_seconds``
by, over the events of the trace viewer's file that the profiler writes
beside the ``.xplane.pb``, whose device events carry their scope as
``args.tf_op``), in percent.  It is what the 80 mixings of a step cost as the program lowers
them: parts bound by latency (a Sinkhorn normalisation is 40 small
operations) beside a step bound by bytes.  Reads nothing for another
configuration, without a device profile, where the trace's events carry no
scope, or on the parent of the PR that added the scopes (no event lies under
them: a share of 0 is not reported either)."""


def read(obs):
    from benchmark import xing_cost

    share = xing_cost.hc_share(obs)
    return 100.0 * share if share else None
