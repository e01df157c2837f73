"""Per-cell roofline contributor profiler (the dry-run 'profiler').

    PYTHONPATH=src python -m repro.launch.contrib --arch yi_34b \
        --shape train_4k --top 12

Prints the top HBM / collective / FLOP contributors with their loop
multipliers and source op_names — what a wall-clock profiler would show,
derived structurally from the compiled HLO (§Perf methodology).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import re
import sys


def top_contributors(text: str, top: int = 12):
    from repro.launch.hlo_analysis import (COLLECTIVES, SKIP_OPS, _BODY_RE,
                                           _CALLS_RE, _COND_RE, _LHS_C_RE,
                                           _SHAPE_RE, _TO_APPLY_RE, _TRIP_RE,
                                           _instr_bytes, _shape_info,
                                           parse_hlo)

    comps = parse_hlo(text)
    entry = next(c for c in comps.values() if c.is_entry)
    sym = {i.name: i.shape for c in comps.values() for i in c.instrs}
    mult = {entry.name: 1.0}
    sched = {entry.name}
    stack = [entry.name]
    while stack:
        cn = stack.pop()
        c = comps.get(cn)
        if c is None:
            continue
        m = mult[cn]
        for ins in c.instrs:
            if ins.op == "while":
                trip = 1
                tm = _TRIP_RE.search(ins.rest)
                if tm:
                    trip = int(tm.group(1))
                for rx in (_BODY_RE, _COND_RE):
                    bm = rx.search(ins.rest)
                    if bm and bm.group(1) in comps:
                        ch = bm.group(1)
                        mult[ch] = mult.get(ch, 0) + m * trip
                        sched.add(ch)
                        stack.append(ch)
            else:
                for rx in (_CALLS_RE, _TO_APPLY_RE):
                    bm = rx.search(ins.rest)
                    if bm and bm.group(1) in comps:
                        ch = bm.group(1)
                        mult[ch] = mult.get(ch, 0) + m
                        stack.append(ch)

    def op_name(ins):
        m = re.search(r'op_name="([^"]+)"', ins.rest)
        return (m.group(1) if m else "?")[-80:]

    byte_rows, coll_rows, flop_rows = [], [], []
    for cn, m in mult.items():
        c = comps.get(cn)
        if c is None:
            continue
        for ins in c.instrs:
            if ins.op in SKIP_OPS or ins.op == "while":
                continue
            if cn in sched:
                b = _instr_bytes(ins, sym, comps) * m
                byte_rows.append((b, m, ins, cn))
                if any(ins.op.startswith(k) for k in COLLECTIVES):
                    coll_rows.append((b, m, ins, cn))
            if ins.op == "dot":
                k = 1
                lm = _LHS_C_RE.search(ins.rest)
                dm = _SHAPE_RE.search(sym.get(ins.operands[0], ""))
                if lm and dm and dm.group(2):
                    dims = [int(x) for x in dm.group(2).split(",")]
                    for ci in (int(x) for x in lm.group(1).split(",") if x):
                        if ci < len(dims):
                            k *= dims[ci]
                flop_rows.append((2.0 * ins.result_elems * k * m, m, ins, cn))

    for title, rows in (("HBM bytes", byte_rows), ("collectives", coll_rows),
                        ("dot FLOPs", flop_rows)):
        rows.sort(key=lambda r: -r[0])
        total = sum(r[0] for r in rows)
        unit = "GF" if "FLOP" in title else "GB"
        print(f"\n== top {title} (total {total/1e9:.1f} {unit}) ==")
        for val, m, ins, cn in rows[:top]:
            print(f"  {val/1e9:9.1f} {unit} x{m:6.0f} {ins.op:20s} "
                  f"{ins.shape[:34]:34s} {op_name(ins)}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    import jax

    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    cell = build_cell(args.arch, args.shape, mesh)
    compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings).lower(
        *cell.args).compile()
    top_contributors(compiled.as_text(), args.top)


if __name__ == "__main__":
    main()
