"""Model + cache: the rows attention read as a share of the rows the lanes'
contexts held: 100 x the sum of ``latent_rows_selected`` over the sum of
``latent_rows_in_context`` of the window's ``serving.decode_step`` spans:
what the selection spared the latent pool's reads (100: no lane was past
``index_topk`` and nothing was spared; the indexer's own reads of the index
pool, a fifth of a latent row a position, are what that costs).  A program
whose spans carry no such attributes records nothing here, and this reads
nothing."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [(a["latent_rows_selected"], a["latent_rows_in_context"])
           for a in (s.get("attrs", {}) for s in obs.get("decode_spans") or [])
           if "latent_rows_selected" in a
           and a.get("latent_rows_in_context")]
    if not got:
        return None
    return 100.0 * sum(s for s, _n in got) / sum(n for _s, n in got)
