"""One benchmark cell, with the program's own record of the run kept beside
its result line:

    python scripts/cell_record.py <out.json> --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs ``chipbench.run`` from the checkout in the working directory, in this
process (a chip belongs to one process), then writes to ``<out.json>`` the
result line, ``TrainRecord.snapshot()`` (what the per-layer readers read:
every tree's row with its tree clock ``done_s`` / ``wait_s`` / ``dispatch_s``
and its pass log ``passes``), the sha256 of the model text cut after 6, 16
and 30 trees (two checkouts that grow the same trees print the same), and
what the record says of the window: for every window tree whether
``len(passes) == hist_passes`` and the passes' rows sum to
``hist_rows_contracted``, and the window trees' periods beside the driver's
``window_s``.  ``scripts/hist_kernel_events.py`` pairs a traced run's kernel
events with the pass log in this file."""

import contextlib
import hashlib
import io
import json
import os
import sys


def window_facts(snap, notes):
    """The record's own account of the window's trees: does every pass log
    add up, and do the trees' periods add up to the driver's window."""
    trees = snap["trees"]
    n = len(notes["hist_passes"])
    rows = trees[-n:]
    ok = [len(r.get("passes", ())) == r["hist_passes"] and
          sum(p["rows"] for p in r["passes"]) == r["hist_rows_contracted"]
          for r in rows]
    done = [r.get("done_s") for r in trees[-n - 1:]]
    periods = [b - a for a, b in zip(done, done[1:])
               if a is not None and b is not None]
    return {"window_trees": n, "pass_logs_add_up": all(ok),
            "periods_s": periods, "periods_sum_s": sum(periods),
            "window_s": notes["window_s"],
            "hist_passes": [r["hist_passes"] for r in rows]}


def main(out, argv):
    sys.path.insert(0, os.getcwd())
    import lightgbm_tpu.basic as basic
    from chipbench import run as cb
    from lightgbm_tpu.telemetry import last_train_record

    shas, text_of = {}, basic.Booster.model_to_string

    def hashed(self, *a, **k):
        text = text_of(self, *a, **k)
        start = text.find("Tree=0\n")
        for cut in (6, 16, 30):
            end = text.find(f"Tree={cut}\n")
            if start >= 0 and end > 0:
                shas[cut] = hashlib.sha256(
                    text[start:end].encode()).hexdigest()[:12]
        return text

    basic.Booster.model_to_string = hashed
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = cb.main(argv)
    sys.stdout.write(printed.getvalue())
    line = json.loads(printed.getvalue().strip().splitlines()[-1])
    rec = last_train_record()
    snap = None if rec is None else rec.snapshot()
    facts = None
    if snap and snap["trees"] and "passes" in snap["trees"][-1]:
        facts = window_facts(snap, line["notes"])
        print(json.dumps({k: v for k, v in facts.items()
                          if k != "periods_s"}), file=sys.stderr)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as fh:
        json.dump({"argv": argv, "line": line, "model_sha": shas,
                   "window": facts, "record": snap}, fh)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
