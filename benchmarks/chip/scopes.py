"""Device time by the program's named scopes: stem, head and each block.

The profiler's ``XLA Ops`` events carry an HLO instruction's name and
text, and not the ``jax.named_scope`` path it was traced under.  That
path sits in the compiled program's HLO text, as each instruction's
``metadata={op_name="jit(timed_step)/mbconv0/..."}``.  ``op_scopes``
reads that text and maps every instruction name to ``(scope, kernel)``:

* ``scope`` is the first ``op_name`` path component that is ``stem``,
  ``head``, ``mbconv<i>`` or ``fusedmb<i>`` (the program's scope names).
  An instruction whose ``op_name`` is an argument of the timed step
  (``x``, the images; ``p['<key>']...``, the weights: a layout copy XLA
  makes of it) goes to the scope that reads that argument: the images
  and ``p['stem']`` to ``stem``, ``p['block<i>']`` to block ``i``, any
  other weight to ``head``.  An instruction with no ``op_name`` at all
  (one XLA added: a weight prefetch, a bitcast) takes the scope of its
  first operand that has one.  A program that names no scope maps
  nothing.
* ``kernel`` is, for a Pallas kernel, the ``name=`` its ``pallas_call``
  passes (the path component just before ``pallas_call``), and ``XLA``
  for every other op: pads, slices, adds, the SE and the stem's and
  head's own ops.

``scope_time`` sums each pair's device time inside the window's timed
steps.  Caveat: a fusion is attributed by its own metadata, which XLA
takes from the fusion's root.  When XLA fuses one scope's op into
another's (a stem activation into block 0's input pad, say) the whole
fusion goes to the scope of its root.

The per-layer readers find the HLO text by compiling the timed step
again as ``run.py`` compiled it (``timed_step_hlo``): the compilation
cache returns the executable that ran, and a recompilation gives the
same instruction names.  Run on its own, this module prints a traced
run's device time by scope:

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \\
        --seconds <s>
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

import reduce

XLA = "XLA"
SCOPE = re.compile(r"(stem|head|mbconv\d+|fusedmb\d+)")
INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+) = ")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
OPERAND = re.compile(r"%([^\s,()=]+)")
WEIGHT = re.compile(r"p\[\\?'(\w+?)(\d*)\\?'\]")
IMAGES = "x"
PLAN_SPAN = "autotune.plan"

Pair = Tuple[str, str]


def is_block(scope: str) -> bool:
    return scope.startswith(("mbconv", "fusedmb"))


def _argument_scope(path: str, blocks: Dict[str, str]) -> Optional[str]:
    if path == IMAGES:
        return "stem"
    m = WEIGHT.match(path)
    if m is None:
        return None
    key, index = m.groups()
    if key == "block" and index:
        return blocks.get(index)
    return "stem" if key + index == "stem" else "head"


def op_scopes(hlo_text: str) -> Dict[str, Pair]:
    """Instruction name -> ``(scope, kernel)`` for every instruction of
    the HLO text that the rules of the module docstring place."""
    rows = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m is not None:
            meta = OP_NAME.search(line, m.end())
            rows.append((m.group(1), line, m.end(),
                         meta.group(1) if meta else ""))
    found = {p for _, _, _, path in rows for p in path.split("/")
             if SCOPE.fullmatch(p)}
    if not found:                     # a program that names no scope
        return {}
    blocks = {re.sub(r"\D", "", s): s for s in found if is_block(s)}
    out: Dict[str, Pair] = {}
    for name, line, end, path in rows:
        parts = path.split("/")
        scope = next((p for p in parts if SCOPE.fullmatch(p)), None) \
            or _argument_scope(path, blocks)
        if scope is None and not path:
            scope = next((out[o][0] for o in OPERAND.findall(line, end)
                          if o in out), None)
        if scope is None:
            continue
        kernel = XLA
        if reduce.is_pallas(line) and "pallas_call" in parts[1:]:
            kernel = parts[parts.index("pallas_call", 1) - 1]
        out[name] = (scope, kernel)
    return out


def instruction(op: reduce.Op) -> str:
    """The HLO instruction name of a trace op (``reduce.op_name`` form)."""
    return op.name.split(" ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class ScopeTime:
    """Device time of the timed steps, split by ``(scope, kernel)``, in
    seconds averaged over devices."""

    steps: int
    step_s: float                 # the timed steps' device time
    pairs: Dict[Pair, float]      # union of each pair's ops, per step
    unattributed_s: float         # ops no rule places

    def share(self, keep) -> float:
        """% of ``step_s`` in the pairs for which ``keep(scope, kernel)``
        holds."""
        return 100.0 * sum(s for (sc, k), s in self.pairs.items()
                           if keep(sc, k)) / self.step_s


def scope_time(trace: reduce.Trace, prefixes: Sequence[str],
               scopes: Dict[str, Pair]) -> ScopeTime:
    """Sums, per ``(scope, kernel)``, the device time of the ops inside
    each timed step: the union of that pair's op intervals, clipped to
    the step."""
    steps = reduce.timed_steps(trace, prefixes)
    n, step_ns, lost = 0, 0, 0
    pairs: Dict[Pair, int] = {}
    for d_ops, d_steps in zip(trace.ops, steps):
        starts = [o.start for o in d_ops]
        for t in d_steps:
            n += 1
            step_ns += t.end - t.start
            groups: Dict[Optional[Pair], List[reduce.Interval]] = {}
            for o in d_ops[bisect.bisect_left(starts, t.start):
                           bisect.bisect_left(starts, t.end)]:
                key = scopes.get(instruction(o))
                groups.setdefault(key, []).append((o.start, o.end))
            for key, iv in groups.items():
                ns = reduce.covered(reduce.union(iv), t.start, t.end)
                if key is None:
                    lost += ns
                else:
                    pairs[key] = pairs.get(key, 0) + ns
    k = max(1, len(trace.ops))
    return ScopeTime(steps=n, step_s=step_ns / k * 1e-9,
                     pairs={p: ns / k * 1e-9 for p, ns in pairs.items()},
                     unattributed_s=lost / k * 1e-9)


def top_pairs(st: ScopeTime, n: int = 10) -> List[List[object]]:
    """The ``n`` pairs with most device time, as ``["scope / kernel",
    seconds]``."""
    top = sorted(st.pairs.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{sc} / {k}", s] for (sc, k), s in top]


def closure(st: ScopeTime) -> Dict[str, float]:
    """The timed steps' device time split four ways, in % of it: Pallas
    kernels, block glue, stem and head, ops no rule places."""
    return {
        "pallas": st.share(lambda sc, k: k != XLA),
        "block_glue": st.share(lambda sc, k: is_block(sc) and k == XLA),
        "stem_head": st.share(lambda sc, k: not is_block(sc)),
        "unattributed": 100.0 * st.unattributed_s / st.step_s,
    }


# -- what the per-layer readers read ------------------------------------------

def timed_step_hlo(cell, batch: int) -> str:
    """The compiled HLO text of the timed step of ``run.run_offline``:
    the same function name and arguments (``p``, ``x``), shapes,
    placement and matmul precision."""
    import contextlib
    import jax
    import numpy as np
    import run
    import weights
    import work
    cfg, res = cell.cfg, cell.mix["resolution"]
    leaves = work.reference_module(cfg).leaves(cfg)
    on = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on),
        jax.eval_shape(lambda: weights.make_params(
            leaves, 0, np.dtype(cfg["dtype"]))))
    x = jax.ShapeDtypeStruct((batch, res, res, 3), np.float32, sharding=on)
    apply = run.program_apply(cfg)

    def timed_step(p, x):
        return apply(p, x)
    precision = cfg.get("matmul_precision")
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        return jax.jit(timed_step).lower(params, x).compile().as_text()


def program_spans() -> Dict[str, dict]:
    """The program's own span aggregates (``repro.core.telemetry``)."""
    from repro.core import telemetry
    return telemetry.snapshot()["spans"]


def _record(reading) -> dict:
    """What the readers of one run share, kept on that run's reading: the
    program's spans as the first reader found them (before
    ``timed_step_hlo`` traces the program again) and the instruction
    map, made once."""
    rec = vars(reading).get("_scopes_record")
    if rec is None:
        rec = {"spans": program_spans(), "scopes": None}
        reading._scopes_record = rec
    return rec


def span_total_s(reading, name: str) -> Optional[float]:
    """``total_s`` of a program span, or None where the program has none."""
    stat = _record(reading)["spans"].get(name)
    return None if stat is None else stat["total_s"]


def step_scope_time(reading) -> Optional[ScopeTime]:
    """The timed steps' device time by scope, or None without a trace or
    where the program names no scope."""
    rec = _record(reading)
    if reading.trace is None:
        return None
    if rec["scopes"] is None:
        rec["scopes"] = op_scopes(timed_step_hlo(reading.cell,
                                                 reading.window.batch))
    if not rec["scopes"]:
        return None
    st = scope_time(reading.trace, reading.window.step_prefixes,
                    rec["scopes"])
    return st if st.step_s > 0 and st.pairs else None


# -- a traced run, by scope ---------------------------------------------------

def main(argv=None) -> int:
    """One ``--trace 1`` run of a cell through ``run.run``; prints one JSON
    object: its result's metrics, the window's images/s, the timed steps'
    device time by scope (top pairs and the four-way closure)."""
    import json
    import sys
    import run
    made = []
    reading = run.Reading
    run.Reading = lambda **kw: made.append(reading(**kw)) or made[-1]
    args = run.parse_args((argv if argv is not None else sys.argv[1:])
                          + ["--trace", "1"])
    result = run.run(args)
    r = made[-1]
    st = step_scope_time(r)
    out = {"metrics": result["metrics"], "correct": result["correct"],
           "traced_images_per_s": r.window.images / r.window.seconds,
           "steps": st.steps if st else 0,
           "step_s": st.step_s if st else 0.0,
           "closure": closure(st) if st else {},
           "scopes": top_pairs(st, 48) if st else [],
           "device_ops": result.get("breakdown", {}).get("device_ops")}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
