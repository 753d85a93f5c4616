#!/usr/bin/env python3
"""Trace device ops back to their HLO instructions and source lines.

Compiles a training cell's iteration for a described, unattached TPU v5e
(nothing runs; ``JAX_PLATFORMS=cpu`` here is fine) and writes the
compiled HLO text, then looks up op names as a profiler trace gives them
(``%copy.12 = s32[...] copy(...)``, e.g. ``unscoped_top`` of
``tools/span_report.py``'s report):

    python3 bench/tools/hlo_sources.py --workload train-pubmed-k1000 \
        --report .bench_traces/reports/train-pubmed-k1000-<seed>-t1.json

For each op it prints the instruction, its own ``op_name`` and source
line, and, where the compiler left it none (layout copies, sort and
scatter expansions), the nearest ones it has: inside its fused
computation, then along its operands and its users."""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import common  # noqa: E402

INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")
COMP = re.compile(r"^(?:ENTRY )?%(\S+) ")
META = re.compile(r'(op_name|stack_frame_id)=("[^"]*"|\d+)')
TABLE = re.compile(r"^(\d+) (.*)$")


def compile_text(workload: str, tokens: int) -> str:
    """The iteration of ``workload``'s configuration, compiled for one
    chip of a described ``v5e:2x2``, as HLO text."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(common.ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as bench_run
    from repro.core.engine.backends import iteration_vmap
    from repro.core.engine.state import MPState
    from repro.kernels import ops
    _, _, cfg, _ = bench_run.load_cell(workload)
    if cfg["num_workers"] != 1 or cfg["blocks_per_worker"] != 1:
        raise SystemExit("only the one-worker, one-block iteration")
    jax.config.update("jax_enable_compilation_cache", False)
    # off the TPU the kernels would otherwise take interpret mode
    ops.resolve_interpret = lambda interpret: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    v, k, d = cfg["vocab_size"], cfg["num_topics"], cfg["num_docs"]
    state = MPState(cdk=s((1, d, k)), ckt=s((1, 1, v, k)),
                    block_id=s((1, 1)), ck_synced=s((k,)),
                    ck_local=s((1, k)), z=s((1, 1, tokens)))
    f32 = jnp.float32
    lowered = iteration_vmap.lower(
        state, s((1, 1, tokens), f32), s((1, 1, tokens)), s((1, 1, tokens)),
        s((1, 1, tokens), jnp.bool_), s((k,), f32), s((), f32), s((), f32),
        sampler_mode=cfg["sampler"], sync_ck=True, data_parallel=1,
        table_lifetime=cfg["table_lifetime"], track_error=False,
        sampler_args=())
    return lowered.compile().as_text()


def frames(text: str) -> Dict[str, str]:
    """``stack_frame_id`` -> ``file:line (function)`` of its innermost
    frame, from the module's ``FileNames`` ... ``StackFrames`` tables."""
    tables: Dict[str, Dict[str, str]] = {}
    cur = None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            cur = tables.setdefault(line, {})
            continue
        m = TABLE.match(line)
        if cur is None or not m:
            if line.startswith(("%", "ENTRY", "HloModule")):
                cur = None
            continue
        cur[m.group(1)] = m.group(2)
    files, funcs = tables.get("FileNames", {}), tables.get("FunctionNames", {})
    locs = {}
    for i, v in tables.get("FileLocations", {}).items():
        f = dict(re.findall(r"(\w+)=(\d+)", v))
        path = files.get(f.get("file_name_id"), "?").strip('"')
        path = path.split("/src/")[-1] if "/src/" in path else path
        func = funcs.get(f.get("function_name_id"), "?").strip('"')
        locs[i] = f"{path}:{f.get('line', '?')} ({func})"
    return {i: locs.get(dict(re.findall(r"(\w+)=(\d+)", v))
                        .get("file_location_id"), "?")
            for i, v in tables.get("StackFrames", {}).items()}


def parse(text: str) -> Dict[str, dict]:
    """Every instruction by name: its computation, opcode, operands, the
    computation it calls and its metadata (``source``: its file and
    line)."""
    out, comp = {}, ""
    src = frames(text)
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m:
            c = COMP.match(line)
            if c and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name, shape, opcode, rest = m.groups()
        meta = {k: v.strip('"') for k, v in META.findall(rest)}
        if "stack_frame_id" in meta:
            meta["source"] = src.get(meta["stack_frame_id"], "?")
        calls = re.search(r"calls=%([\w.-]+)", rest)
        out[name] = {"comp": comp, "shape": shape, "opcode": opcode,
                     "operands": re.findall(r"%([\w.-]+)",
                                            rest.split("), ")[0]),
                     "calls": calls.group(1) if calls else None,
                     "meta": meta, "text": line.strip()[:240]}
    return out


def where(meta: dict) -> str:
    if not meta.get("op_name"):
        return ""
    return f"{meta['op_name']}  [{meta.get('source', '?')}]"


def explain(name: str, hlo: Dict[str, dict]) -> List[str]:
    """Where instruction ``name`` came from, as far as the text says."""
    ins = hlo.get(name)
    if ins is None:
        return ["  (no instruction of that name in the compiled text)"]
    lines = [f"  {ins['text']}", f"  own: {where(ins['meta']) or '-'}"]
    if ins["calls"]:
        inner = sorted({where(i["meta"]) for i in hlo.values()
                        if i["comp"] == ins["calls"] and i["meta"]} - {""})
        lines += [f"  fused: {w}" for w in inner[:6]]
    users = [u for u, i in hlo.items() if name in i["operands"]
             and i["comp"] == ins["comp"]]
    seen, frontier = set(), list(ins["operands"])
    for hop in range(1, 4):
        nxt = []
        for op in frontier:
            if op in seen or op not in hlo:
                continue
            seen.add(op)
            w = where(hlo[op]["meta"])
            if w:
                lines.append(f"  operand ({hop} up) %{op}: {w}")
            else:
                nxt += hlo[op]["operands"]
        frontier = nxt
    for u in users[:4]:
        w = where(hlo[u]["meta"])
        lines.append(f"  user %{u} ({hlo[u]['opcode']}): {w or '-'}")
    return lines


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="train-pubmed-k1000")
    ap.add_argument("--tokens", type=int, default=11_520_000,
                    help="token slots of the block (the corpus's size)")
    ap.add_argument("--hlo", help="read this compiled text, don't compile")
    ap.add_argument("--out", default=os.path.join(
        common.ROOT, ".bench_traces", "reports", "iteration.hlo.txt"))
    ap.add_argument("--report", help="a span_report JSON to explain")
    ap.add_argument("names", nargs="*", help="trace op names to explain")
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    if args.hlo:
        with open(args.hlo) as f:
            text = f.read()
    else:
        text = compile_text(args.workload, args.tokens)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
        common.log(f"compiled text: {args.out}")
    hlo = parse(text)
    names = list(args.names)
    if args.report:
        with open(args.report) as f:
            rep = json.load(f)
        names += [row[0] for row in rep["spans"]["unscoped_top"]]
    for full in names:
        m = re.match(r"%?([\w.-]+)", full.split(" = ")[0].strip())
        print(f"{full[:160]}")
        print("\n".join(explain(m.group(1), hlo)))


if __name__ == "__main__":
    main()
