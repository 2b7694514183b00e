#!/usr/bin/env python3
"""Split the device time of the ``haswell.grid`` cell by named scope.

Runs the benchmark cell's grid (``bench/configs/haswell.json``,
``bench/traffic/grid.json``) on the chip twice in one process: a warm-up
grid that compiles every program, then a grid profiled for ``--length``
seconds from ``--offset`` seconds into it.  Each device op event of the
trace is named by its HLO line; it is matched, by op name and result
shape, to the instructions of the chunk programs the engine compiled, and
its self time is booked to a scope:

1. the innermost ``pass.*``, ``sched.*``, ``chunk.*`` or ``metrics.*``
   component of the instruction's own ``op_name`` metadata;
2. else, in a ``lax.cond`` branch, the scope of the conditional;
3. else the scope of its nearest scoped consumers in the program, where
   they agree (``consumers disagree`` where not): ``jnp.cumsum`` lowers on
   TPU with an empty name stack, and fusions a compiler pass made carry no
   metadata;
4. else ``no scope``; an event that matches no chunk program is booked
   to ``other program``, and one whose name and shape carry different
   scopes in different programs to ``ambiguous``.

Self time is also split by HLO opcode (a fusion that holds a convolution
reads ``fusion(convolution)``).  Prints one JSON object and writes it to
``--out``.

  python3 tools/device_scopes.py --seed 5214000031 \
      --out chiprun_out/scopes.json
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import re
import sys
import tempfile
import types
from typing import Dict, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SCOPE = re.compile(r"^(pass|sched|chunk|metrics)\.\w+$")
HEAD = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
CALLEES = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def split_shape(rest: str) -> Tuple[str, str]:
    """``(shape, remainder)`` of the text after ``name = ``; a tuple shape
    holds parentheses of its own."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return rest[:i + 1], rest[i + 1:].lstrip()
    return rest, ""


def parse_line(line: str):
    """``(name, shape, opcode, operand names)`` of one HLO instruction
    line, or None."""
    m = HEAD.match(line)
    if m is None:
        return None
    shape, tail = split_shape(line[m.end():])
    opcode, _, args = tail.partition("(")
    depth, end = 1, 0
    for end, ch in enumerate(args):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            break
    return (m.group(1), shape, opcode,
            re.findall(r"%([\w.\-]+)", args[:end]))


def own_scope(line: str):
    m = re.search(r'op_name="([^"]*)"', line)
    if m is None:
        return None
    parts = [p for p in m.group(1).split("/") if SCOPE.match(p)]
    return parts[-1] if parts else None


def program_scopes(text: str) -> Dict[Tuple[str, str], Tuple[str, str]]:
    """``{(op name, shape): (scope, opcode)}`` of one compiled HLO module."""
    comp_of, lines, called_by, conv_comps = {}, {}, {}, set()
    users = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        parsed = parse_line(line)
        if parsed is None:
            continue
        name = parsed[0]
        comp_of[name], lines[name] = comp, line
        if parsed[2] == "convolution":
            conv_comps.add(comp)
        for operand in parsed[3]:
            users[operand].append(name)
        for one, many in CALLEES.findall(line):
            for callee in ([one] if one else many.split(",")):
                called_by[callee.strip().lstrip("%")] = name

    def scope(name):
        s = own_scope(lines[name])
        if s is not None:
            return s
        caller = called_by.get(comp_of[name])
        while caller is not None:
            s = own_scope(lines[caller])
            if s is not None and parse_line(lines[caller])[2] == "conditional":
                return s
            caller = called_by.get(comp_of[caller])
        frontier, seen = users[name], set()
        while frontier:
            found = {own_scope(lines[u]) for u in frontier} - {None}
            if found:
                return found.pop() if len(found) == 1 else \
                    "consumers disagree"
            seen.update(frontier)
            frontier = [v for u in frontier for v in users[u]
                        if v not in seen]
        return "no scope"

    out = {}
    for name, line in lines.items():
        _, shape, opcode, _ = parse_line(line)
        m = re.search(r"calls=%([\w.\-]+)", line)
        if opcode == "fusion" and m and m.group(1) in conv_comps:
            opcode = "fusion(convolution)"
        out[(name, shape)] = (scope(name), opcode)
    return out


def chunk_programs() -> list:
    """Compiled HLO text of every chunk program this process ran."""
    from repro.sweep import batch as sb

    texts = []
    for key in sorted(sb._COMPILED_KEYS | set(sb._WARM_EXECUTABLES),
                      key=repr):
        exe = sb._WARM_EXECUTABLES.get(key)
        if exe is None:
            cfg, n, B, W, lo, hi, span, classes, sjf, depth = key
            exe = sb._chunk_fn(cfg, n, B, W, lo, hi, span, classes,
                               with_sjf=sjf, depth_bounded=depth).lower(
                *sb.chunk_arg_shapes(n, B)).compile()
        texts.append(exe.as_text())
    return texts


def split(profile, lo, hi, texts) -> Dict:
    from bench.lib import xplane

    table: Dict = {}
    for text in texts:
        for key, val in program_scopes(text).items():
            table[key] = val if table.get(key, val) == val else \
                ("ambiguous", val[1])
    by_scope, by_opcode, busy = {}, {}, []
    for ops in xplane.device_ops(profile).values():
        scoped, coded = [], []
        for name, s, e in ops:
            parsed = parse_line(name)
            key = parsed[:2] if parsed else None
            sc, op = table.get(key, ("other program",
                                     parsed[2] if parsed else "?"))
            scoped.append((sc, s, e))
            coded.append((op, s, e))
        # self_times keys by the label each event now carries
        for events, into in ((scoped, by_scope), (coded, by_opcode)):
            for k, t in xplane.self_times(events, lo, hi).items():
                into[k] = into.get(k, 0) + t / 1e9
        busy.append(sum(e - s for s, e in xplane.union(
            xplane.clip([(s, e) for _, s, e in ops], lo, hi))) / 1e9)

    def largest_first(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))

    return {"window_s": (hi - lo) / 1e9, "busy_s": sum(busy) / len(busy),
            "by_scope": largest_first(by_scope),
            "by_opcode": largest_first(by_opcode)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--offset", type=float, default=6.0)
    ap.add_argument("--length", type=float, default=1.0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "device_scopes.json"))
    args = ap.parse_args(argv)

    import jax

    from bench.lib import grid, xplane
    from bench.lib.harness import Profiler, load_json
    from repro.xla_cache import enable_compilation_cache

    if jax.devices()[0].platform != "tpu":
        print("device_scopes: needs a TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    ctx = types.SimpleNamespace(
        cfg=load_json(ROOT / "bench" / "configs" / "haswell.json"),
        traffic=load_json(ROOT / "bench" / "traffic" / "grid.json"),
        seed=args.seed)
    spec = grid.spec_for(ctx)
    with tempfile.TemporaryDirectory() as tmp:
        tmp = pathlib.Path(tmp)
        grid.one_grid(ctx, spec, tmp / "warmup")
        prof = Profiler(tmp / "profile", args.offset, args.length)
        prof.start()
        grid.one_grid(ctx, spec, tmp / "profiled")
        prof.finish()
        profile = xplane.load(xplane.find_xplane(str(tmp / "profile")))
        bounds = xplane.annotation_bounds(profile, "bench.profiled")
        out = split(profile, *bounds, chunk_programs())
    out.update(seed=args.seed, device=jax.devices()[0].device_kind)
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
