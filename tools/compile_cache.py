"""Manage the persistent two-tier compilation cache (core/compile_cache.py).

Usage:
    python tools/compile_cache.py stats   [--dir DIR] [--json]
    python tools/compile_cache.py ls      [--dir DIR]
    python tools/compile_cache.py clear   [--dir DIR]
    python tools/compile_cache.py prewarm [--dir DIR] --model NAME
                                          [--model NAME ...] [--batch N]

``stats``/``ls`` inspect the tier-B AOT entries (plus the tier-A XLA file
footprint); ``clear`` wipes both tiers.  ``prewarm`` builds bundled models
from ``models.bundled_builders()`` (the same zoo tools/proglint.py lints)
and runs ``Executor.warmup`` on each, so a later process — a trainer, an
elastic re-quorum, a serving bucket — starts with its executables already
on disk and pays a restore instead of an XLA compile.

The cache location comes from FLAGS_compile_cache_dir (env) or --dir.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _human(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return ("%.1f%s" if unit != "B" else "%d%s") % (n, unit)
        n /= 1024.0


def cmd_stats(cc, args):
    st = cc.stats()
    if args.json:
        json.dump(st, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return 0
    print("cache dir : %s%s" % (st["dir"] or "(unset)",
                                "" if st["enabled"] else "  [disabled]"))
    print("tier B    : %d entries (%d valid), %s / cap %s"
          % (st["aot_entries"], st["aot_valid"], _human(st["aot_bytes"]),
             _human(st["max_bytes"])))
    print("tier A    : %d XLA files, %s" % (st["xla_files"],
                                            _human(st["xla_bytes"])))
    return 0


def cmd_ls(cc, args):
    ents = cc.entries()
    if not ents:
        print("(no tier-B entries under %s)" % (cc.cache_dir() or "(unset)"))
        return 0
    print("%-14s %-9s %-6s %-12s %-19s meta" % ("key", "bytes", "valid",
                                                "jax", "last_used"))
    for r in ents:
        print("%-14s %-9s %-6s %-12s %-19s %s"
              % (r["key"][:12] + "..", _human(r["bytes"]),
                 "ok" if r["valid"] else "BAD", r["jax"] or "?",
                 time.strftime("%Y-%m-%d %H:%M:%S",
                               time.localtime(r["last_used"])),
                 json.dumps(r["meta"], sort_keys=True)))
    return 0


def cmd_clear(cc, args):
    st = cc.stats()
    cc.clear()
    print("cleared %d tier-B entries (%s) + %d tier-A files (%s) under %s"
          % (st["aot_entries"], _human(st["aot_bytes"]), st["xla_files"],
             _human(st["xla_bytes"]), cc.cache_dir()))
    return 0


def cmd_prewarm(cc, args):
    import paddle_tpu as fluid
    from paddle_tpu import models

    if not cc.enabled():
        print("error: no cache dir (set JAX_COMPILATION_CACHE_DIR, "
              "FLAGS_compile_cache_dir or --dir)",
              file=sys.stderr)
        return 2
    builders = models.bundled_builders()
    names = args.model or sorted(builders)
    unknown = [n for n in names if n not in builders]
    if unknown:
        print("error: unknown model(s) %s (have: %s)"
              % (unknown, ", ".join(sorted(builders))), file=sys.stderr)
        return 2
    rc = 0
    for name in names:
        t0 = time.perf_counter()
        with fluid.unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 1
            with fluid.program_guard(main, startup):
                feeds, fetches = builders[name]()
        specs = {}
        for v in feeds:
            shape = tuple(args.batch if d == -1 else int(d)
                          for d in v.shape)
            specs[v.name] = (shape, v.dtype)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            try:
                got = exe.warmup(main, feed_specs=specs,
                                 fetch_list=[v.name for v in fetches])
            except Exception as e:
                print("%-18s FAILED: %s" % (name, e), file=sys.stderr)
                rc = 1
                continue
        print("%-18s %-8s key=%s.. %.0fms"
              % (name, got["source"], (got.get("key") or "?")[:12],
                 (time.perf_counter() - t0) * 1e3))
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="inspect / manage the persistent compilation cache")
    ap.add_argument("--dir", help="cache directory (overrides "
                    "FLAGS_compile_cache_dir)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("stats").add_argument("--json", action="store_true",
                                         help="machine-readable stats")
    sub.add_parser("ls")
    sub.add_parser("clear")
    pw = sub.add_parser("prewarm")
    pw.add_argument("--model", action="append", metavar="NAME",
                    help="bundled model to pre-compile (repeatable; "
                    "default all of models.bundled_builders())")
    pw.add_argument("--batch", type=int, default=8,
                    help="batch substituted for -1 feed dims (default 8)")
    args = ap.parse_args(argv)

    if args.dir:
        os.environ["FLAGS_compile_cache_dir"] = args.dir
    import paddle_tpu as fluid  # noqa: F401  (flags read env at import)
    from paddle_tpu.core import compile_cache as cc

    if args.dir:
        fluid.set_flags({"FLAGS_compile_cache_dir": args.dir})
    return {"stats": cmd_stats, "ls": cmd_ls, "clear": cmd_clear,
            "prewarm": cmd_prewarm}[args.cmd](cc, args)


if __name__ == "__main__":
    sys.exit(main())
