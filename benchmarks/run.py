# One function per paper table. Print ``name,us_per_call,derived`` CSV
# and write one ``BENCH_<name>.json`` per registered benchmark at the
# repo root (fixed RNG seeds throughout, so every emitted number is
# reproducible run-to-run).  Each JSON keeps a ``trajectory`` list --
# one timestamped entry appended per run -- so the numbers' history
# across commits/runs is preserved instead of overwritten; the latest
# entry is mirrored at the top level for dashboards that read one run.
import datetime
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from benchmarks import adaptive_precision, bank_scaling, channel_scaling, \
    host_lane_scaling, indram_ops, kernel_wallclock, paper_figs, \
    serving_load, session_scaling


def _paper_figs():
    return [row for fig in paper_figs.ALL_FIGS for row in fig()]


#: name -> zero-arg callable returning [(name, us_per_call, derived)].
#: Every entry gets its own ``BENCH_<name>.json`` at the repo root.
REGISTRY = {
    "paper_figs": _paper_figs,
    "kernel_wallclock": kernel_wallclock.run,
    "bank_scaling": bank_scaling.run,
    "channel_scaling": channel_scaling.run,
    "session_scaling": session_scaling.run,
    "host_lane_scaling": host_lane_scaling.run,
    "indram_ops": indram_ops.run,
    "serving_load": serving_load.run,
    "adaptive_precision": adaptive_precision.run,
}


def write_json(name: str, rows) -> str:
    """Append this run to ``BENCH_<name>.json``'s ``trajectory`` (and
    mirror it at the top level as the latest entry).  A pre-trajectory
    file's single run is preserved as the first trajectory entry."""
    path = os.path.join(ROOT, f"BENCH_{name}.json")
    trajectory = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            trajectory = prev.get("trajectory")
            if trajectory is None:           # legacy single-run layout
                trajectory = [{"ts": prev.get("ts"),
                               "rows": prev.get("rows", [])}]
        except (json.JSONDecodeError, OSError):
            trajectory = []
    entry = {
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows],
    }
    trajectory.append(entry)
    payload = {
        "benchmark": name,
        "columns": ["name", "us_per_call", "derived"],
        "ts": entry["ts"],
        "rows": entry["rows"],
        "trajectory": trajectory,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def main() -> None:
    print("name,us_per_call,derived")
    for bench, fn in REGISTRY.items():
        rows = fn()
        for name, us, derived in rows:
            print(f"{name},{us},{derived}")
        write_json(bench, rows)


if __name__ == '__main__':
    main()
