"""The benchmark's one traffic generator for serving cells.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``); this module
turns it into requests and, run as a program, sends them to a server over
the native RPC wire and records what a client sees.  It runs as a child of
the process that holds the chip and never opens a JAX backend itself (the
parent starts it with ``JAX_PLATFORMS=cpu``); its record goes to the parent
as one JSON object on standard output.

The loop is closed: ``clients`` callers, each sending its next request when
the previous one completed.  Parameters of a mix (all sizes in tokens):

``prompt_len`` /  ``{"dist": "log_uniform"|"uniform", "min", "max"}``: the
``output_len``    continuous distribution a request's length is drawn from.
``size_set``      how many (prompt, output) pairs are drawn.  The set is the
                  mix's own (stratified quantiles of the two distributions,
                  paired by ``schedule_seed``); ``--seed`` sets the order in
                  which the callers walk it, each caller's place in its
                  first request, and every token id.  Every seed thus sends
                  the same set of sizes in another order: runs differ by the
                  system's timing and by which requests the window catches,
                  not by the luck of how long the drawn requests are.
``ramp_s``        the callers all send their first request this long
                  before the window: time for the server to admit them (it
                  takes requests sent together one at a time, over several
                  seconds; PERF.md section 6) and to feed their prompts.
                  Each caller's first request is cut to a seeded share of
                  its output length (its place in a request that was
                  already running), so that completions and admissions are
                  spread over the window from its start instead of arriving
                  together one request-lifetime later.

Times are ``time.monotonic()``, which parent and child share.
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np


def quantiles(spec, n):
    """``n`` lengths at the stratified quantiles (i + 1/2) / n of ``spec``."""
    lo, hi = float(spec["min"]), float(spec["max"])
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "log_uniform":
        vals = lo * (hi / lo) ** qs
    elif spec["dist"] == "uniform":
        vals = lo + (hi - lo) * qs
    else:
        raise ValueError("unknown length distribution %r" % spec["dist"])
    return [int(round(v)) for v in vals]


def size_set(traffic, max_seq):
    """The mix's (prompt_len, output_len) pairs, the same for every seed."""
    n = int(traffic["size_set"])
    prompts = quantiles(traffic["prompt_len"], n)
    outputs = quantiles(traffic["output_len"], n)
    pairing = np.random.default_rng(
        int(traffic["schedule_seed"])).permutation(n)
    pairs = [(prompts[i], outputs[j]) for i, j in enumerate(pairing)]
    bad = [po for po in pairs if sum(po) > max_seq]
    if bad:
        raise ValueError("prompt + output exceeds the model's %d positions: "
                         "%s" % (max_seq, bad[:3]))
    return pairs


class Schedule:
    """What each caller sends, from the mix and the seed."""

    def __init__(self, traffic, max_seq, vocab, seed):
        self.pairs = size_set(traffic, max_seq)
        self.clients = int(traffic["clients"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 1 << 22])
        self.order = rng.permutation(len(self.pairs))
        # stratified, so that the first completions are evenly spread
        self.first_share = (rng.permutation(self.clients) + 0.5) \
            / self.clients

    def sizes(self, client, k):
        """(prompt_len, max_new) of caller ``client``'s k-th request."""
        p, o = self.pairs[self.order[
            (client + k * self.clients) % len(self.pairs)]]
        if k == 0:
            o = max(int(round(o * self.first_share[client])), 1)
        return p, o

    def prompt(self, client, k, prompt_len):
        rng = np.random.default_rng([self.seed, int(client), int(k)])
        return [int(t) for t in rng.integers(0, self.vocab, prompt_len)]


def send(client, model, traffic, row, prompt):
    """One request through ``ServingClient.generate``; fills ``row``."""
    times = row["token_times"]
    row["t_send"] = time.monotonic()
    reply = client.generate(
        model, prompt, max_new_tokens=row["max_new"],
        deadline_ms=float(traffic["deadline_ms"]), stream=True,
        on_token=lambda _i, _t: times.append(time.monotonic()))
    row["t_done"] = time.monotonic()
    row["status"] = reply.status
    row["error"] = reply.error
    tokens = reply.outputs.get("tokens")
    row["n_tokens"] = 0 if tokens is None else int(np.asarray(tokens).size)


def run(args, traffic):
    # imported here: the parent imports this module for the schedule only
    from paddle_tpu.serving import ServingClient

    lock = threading.Lock()
    rows = []
    t_open = args.t0
    t_stop = args.t0 + args.seconds + args.extra
    ramp = float(traffic["ramp_s"])
    schedule = Schedule(traffic, args.max_seq, args.vocab, args.seed)

    def caller(i):
        client = ServingClient(endpoints=[args.endpoint])
        time.sleep(max(t_open - ramp - time.monotonic(), 0.0))
        k = 0
        while time.monotonic() < t_stop:
            p, o = schedule.sizes(i, k)
            row = {"client": i, "k": k, "prompt_len": p, "max_new": o,
                   "token_times": [], "t_send": None, "t_done": None,
                   "status": None, "error": None, "n_tokens": 0}
            with lock:
                rows.append(row)
            send(client, args.model, traffic, row, schedule.prompt(i, k, p))
            k += 1

    for i in range(schedule.clients):
        threading.Thread(target=caller, args=(i,), daemon=True).start()
    time.sleep(max(t_stop - time.monotonic(), 0.0))
    # every request sent inside the window is owed a first token (or its
    # end) before the record is cut; the rest are abandoned in flight
    t_end = t_open + args.seconds
    give_up = time.monotonic() + float(traffic["deadline_ms"]) / 1e3
    while time.monotonic() < give_up:
        with lock:
            owed = [r for r in rows
                    if r["t_send"] is not None and r["t_send"] < t_end
                    and not r["token_times"] and r["status"] is None]
        if not owed:
            break
        time.sleep(0.05)
    with lock:
        record = [dict(r, token_times=list(r["token_times"])) for r in rows]
    sys.stdout.write(json.dumps({"requests": record}) + "\n")
    sys.stdout.flush()
    # client threads still wait on abandoned requests: leave without them
    os._exit(0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--endpoint", required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--traffic", required=True,
                    help="the mix, as a JSON object (the parent has already "
                    "applied any test-only overrides)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--max-seq", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--extra", type=float, default=0.0,
                    help="keep the load up this long after the window (the "
                    "traced run profiles then)")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("loadgen: refusing to run without JAX_PLATFORMS=cpu: this "
              "process must never reach for the chip", file=sys.stderr)
        return 2
    # imported before the clock starts: the parent fixes the window's start
    # once this process says it is ready, and answers with it
    import paddle_tpu.serving  # noqa: F401

    sys.stdout.write("ready\n")
    sys.stdout.flush()
    args.t0 = float(sys.stdin.readline())
    run(args, json.loads(args.traffic))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
