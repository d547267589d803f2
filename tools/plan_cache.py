"""Plan-cache operations CLI — show/clear/export for the measured
execution plans (oni_ml_tpu/plans):

    python tools/plan_cache.py show [--knob NAME] [--all-backends]
    python tools/plan_cache.py clear
    python tools/plan_cache.py export [DEST]

`show` prints the resolved view: one JSON line per entry (latest per
(knob, backend, shape)), plus a header naming the store path and this
process's fingerprints so "why didn't my entry match" is answerable at
a glance.  By default only entries matching THIS host/backend print;
`--all-backends` shows everything.

`clear` removes the cache file.

`export` writes the current resolved entries as a standalone JSONL
stream (stdout, or DEST) in the store's own line format, so a
session's measurements can be carried to another machine's cache.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _store():
    from oni_ml_tpu import plans

    return plans.default_store()


def cmd_show(args) -> int:
    from oni_ml_tpu import plans

    store = _store()
    fps = {plans.host_fingerprint()}
    header = {
        "store": store.path,
        "schema": plans.SCHEMA_VERSION,
        "host": plans.host_fingerprint(),
        "dropped_records": store.dropped_records,
    }
    if not args.no_device:
        header["backend"] = plans.device_fingerprint()
        fps.add(header["backend"])
    print(json.dumps(header), flush=True)
    for e in sorted(store.entries(), key=lambda e: e.key):
        if args.knob and e.knob != args.knob:
            continue
        if not args.all_backends and e.backend not in fps:
            continue
        print(json.dumps({
            "knob": e.knob, "backend": e.backend, "shape": e.shape,
            "value": e.value, "source": e.source,
            **({"measurements": e.measurements} if e.measurements else {}),
        }), flush=True)
    return 0


def cmd_clear(args) -> int:
    store = _store()
    existed = os.path.exists(store.path)
    store.clear()
    print(json.dumps({"cleared": store.path, "existed": existed}),
          flush=True)
    return 0


def cmd_export(args) -> int:
    from oni_ml_tpu.plans.store import SCHEMA_VERSION

    store = _store()
    out = open(args.dest, "w") if args.dest else sys.stdout
    try:
        for e in sorted(store.entries(), key=lambda e: e.key):
            if args.knob and e.knob != args.knob:
                continue
            rec = {k: v for k, v in e.record.items()
                   if k not in ("seq", "t", "mono_ns")}
            rec["schema"] = SCHEMA_VERSION
            out.write(json.dumps(rec) + "\n")
    finally:
        if args.dest:
            out.close()
            print(json.dumps({"exported": args.dest}), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="plan_cache",
        description="show/clear/export the measured-plan cache "
        "(oni_ml_tpu/plans)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    show = sub.add_parser("show", help="print resolved entries")
    show.add_argument("--knob", default=None)
    show.add_argument("--all-backends", action="store_true",
                      help="include entries for other fingerprints")
    show.add_argument("--no-device", action="store_true",
                      help="skip the device fingerprint (does not "
                      "initialize a jax backend; host-scoped view only)")
    sub.add_parser("clear", help="remove the cache file")
    exp = sub.add_parser("export",
                         help="write entries as a JSONL stream")
    exp.add_argument("dest", nargs="?", default=None)
    exp.add_argument("--knob", default=None)
    args = p.parse_args(argv)
    return {"show": cmd_show, "clear": cmd_clear,
            "export": cmd_export}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
