"""Assemble EXPERIMENTS.md tables from results/*.json artifacts."""

import json
import os


def gb(x):
    return "-" if x in (None, -1) else f"{x / 2**30:.2f}"


DRYRUN_PATHS = ("results/dryrun_all.json", "results/dryrun_moe_refresh.json",
                "results/dryrun_moe2.json", "results/dryrun_small_refresh.json",
                "results/dryrun_small2.json",
                "results/dryrun_mdp_refresh.json")


def dryrun_table(paths=DRYRUN_PATHS):
    d = {}
    for p in paths:  # later files overwrite earlier cells (refreshes win)
        if os.path.exists(p):
            d.update(json.load(open(p)))
    lines = ["| cell | mesh | status | lower+compile s | temp GB/dev | "
             "args GB/dev | AG | AR | RS | A2A | CP |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for key, r in sorted(d.items()):
        parts = key.rsplit("/", 1)
        cell, mesh = parts[0], parts[1]
        if r["status"] != "ok":
            lines.append(f"| {cell} | {mesh} | FAIL | - | - | - |  |  |  |  |  |")
            continue
        c = r.get("collective_counts", {})
        lines.append(
            f"| {cell} | {mesh} | ok | "
            f"{r['lower_s'] + r['compile_s']:.0f} | "
            f"{gb(r.get('temp_size_in_bytes'))} | "
            f"{gb(r.get('argument_size_in_bytes'))} | "
            f"{c.get('all-gather', 0)} | {c.get('all-reduce', 0)} | "
            f"{c.get('reduce-scatter', 0)} | {c.get('all-to-all', 0)} | "
            f"{c.get('collective-permute', 0)} |")
    return "\n".join(lines)


if __name__ == "__main__":
    os.makedirs("results", exist_ok=True)
    with open("results/tables.md", "w") as f:
        f.write("## Dry-run\n\n" + dryrun_table() + "\n")
    print("wrote results/tables.md")
