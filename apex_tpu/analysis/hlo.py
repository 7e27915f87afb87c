"""Structural parsing of lowered StableHLO text (and, at the end, of a
compiled module's optimised HLO text: :func:`instruction_scopes`).

The lint rules (``apex_tpu/analysis/rules.py``) need a handful of facts
about a ``jax.jit(...).lower(...)`` artifact that the ad-hoc test greps
(``"callback" not in lowered.as_text()``) approximated badly: WHICH
custom-call targets appear (a comment or a backend_config hex string
containing the substring must not count), which element types any
tensor in the module uses, and the entry computation's argument/result
attributes (``tf.aliasing_output`` donation marks, ``mhlo.sharding``
annotations, ``mhlo.num_partitions``). Everything here is plain-text
parsing — no XLA compile, no device — so a lint stays trace-only.

The parsers are deliberately line-oriented: ``lowered.as_text()`` prints
one op per line, and the few multi-line constructs (the entry signature,
dense constant payloads) are handled explicitly. Unknown constructs
degrade to "not matched", never to an exception — a lint pass must not
crash on an HLO shape it has never seen.
"""

import collections
import re

# element-type byte widths for tensor<...> size accounting; anything
# unknown falls back to 4 so a size threshold still has a defined value
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8E4M3FN": 1, "f8E5M2": 1,
    "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i1": 1,
    "ui64": 8, "ui32": 4, "ui16": 2, "ui8": 1,
    "complex<f32>": 8, "complex<f64>": 16,
}

_TENSOR_RE = re.compile(r"tensor<([^<>]*(?:<[^<>]*>)?[^<>]*)>")
_CUSTOM_CALL_RE = re.compile(r"stablehlo\.custom_call\s+@([\w.$\-]+)")
_NUM_PARTITIONS_RE = re.compile(r"mhlo\.num_partitions\s*=\s*(\d+)")
_SHARDING_ATTR_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_ALIAS_ATTR_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")


def parse_tensor_type(spec):
    """``'8x128xf32'`` -> ``(shape_tuple, dtype_str, nbytes)``.

    Dynamic or otherwise unparseable dimensions yield shape ``None``
    (size unknown -> nbytes 0, so thresholds never fire spuriously).
    """
    parts = spec.strip().split("x")
    dtype = parts[-1]
    dims = parts[:-1]
    shape = []
    for d in dims:
        if not d.isdigit():
            return None, dtype, 0
        shape.append(int(d))
    n = 1
    for d in shape:
        n *= d
    return tuple(shape), dtype, n * _DTYPE_BYTES.get(dtype, 4)


def custom_call_targets(text):
    """``{target_name: count}`` over every ``stablehlo.custom_call``
    in the module — the precise replacement for the substring grep."""
    out = {}
    for m in _CUSTOM_CALL_RE.finditer(text):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


def num_partitions(text):
    """The module's ``mhlo.num_partitions`` (1 when unannotated)."""
    m = _NUM_PARTITIONS_RE.search(text)
    return int(m.group(1)) if m else 1


def find_dtype_lines(text, dtype):
    """``[(lineno, stripped_line)]`` for lines containing a tensor of
    ``dtype`` — used to name the offending op for the no-f64 rule. The
    match is against parsed tensor types, not the raw substring, so
    ``f64`` inside a constant payload or a name never counts."""
    hits = []
    for i, line in enumerate(text.splitlines(), 1):
        if dtype not in line:
            continue
        for m in _TENSOR_RE.finditer(line):
            if parse_tensor_type(m.group(1))[1] == dtype:
                hits.append((i, line.strip()))
                break
    return hits


def _split_top_level(s, sep=","):
    """Split ``s`` on ``sep`` at bracket depth 0 (handles the nested
    ``tensor<...>`` / ``{...}`` attribute groups in a signature)."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "<{([":
            depth += 1
        elif ch in ">})]":
            depth -= 1
        if ch == sep and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def _balanced_span(text, start):
    """Return the index just past the ``(``...``)`` group opening at
    ``text[start]`` (which must be '(')."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def entry_signature(text):
    """Parse the ``@main`` entry function signature.

    Returns ``{"args": [...], "results": [...]}`` where each entry is
    ``{"type": raw tensor spec or None, "shape", "dtype", "nbytes",
    "sharding": mhlo.sharding or None, "aliased_output": int or None}``
    (results carry no ``aliased_output``). An unparseable signature
    yields empty lists — rules treat that as "no evidence".
    """
    empty = {"args": [], "results": []}
    m = re.search(r"func\.func\s+(?:public\s+)?@main\s*\(", text)
    if not m:
        return empty
    args_open = m.end() - 1
    args_close = _balanced_span(text, args_open)
    args_raw = text[args_open + 1:args_close - 1]
    rest = text[args_close:]
    results_raw = ""
    arrow = re.match(r"\s*->\s*", rest)
    if arrow:
        after = rest[arrow.end():]
        if after.startswith("("):
            results_raw = after[1:_balanced_span(after, 0) - 1]
        else:
            # single un-parenthesized result: up to the opening brace
            results_raw = after.split("{", 1)[0]
            # ... unless the result carries an attribute dict; the
            # parenthesized form is what jax emits, so keep this simple
    sig = {"args": [], "results": []}
    for section, raw in (("args", args_raw), ("results", results_raw)):
        for item in _split_top_level(raw):
            tm = _TENSOR_RE.search(item)
            if tm is None:
                entry = {"type": None, "shape": None, "dtype": None,
                         "nbytes": 0, "sharding": None,
                         "aliased_output": None}
            else:
                shape, dtype, nbytes = parse_tensor_type(tm.group(1))
                sm = _SHARDING_ATTR_RE.search(item)
                am = _ALIAS_ATTR_RE.search(item)
                entry = {"type": tm.group(1), "shape": shape,
                         "dtype": dtype, "nbytes": nbytes,
                         "sharding": sm.group(1) if sm else None,
                         "aliased_output":
                             int(am.group(1)) if am else None}
            sig[section].append(entry)
    return sig


_SHARDING_OP_RE = re.compile(
    r"(%[\w#.]+)\s*=\s*stablehlo\.custom_call\s+@Sharding\((%[\w#.]+)\)")


def sharding_custom_calls(text):
    """``[(lineno, sharding_str, tensor_spec)]`` for every
    ``custom_call @Sharding`` op that is a genuine sharding constraint
    on an intermediate (``with_sharding_constraint`` / committed
    ``device_put`` inside the program).

    ``shard_map`` lowers its input/output marshaling to ``@Sharding``
    ops immediately feeding ``@SPMDFullToShardShape`` (or consuming
    ``@SPMDShardToFullShape``) — those encode the BOUNDARY layout the
    caller asked for (replicated params across a dp mesh is the DDP
    contract, not a blowup), so they are excluded here."""
    lines = text.splitlines()
    # vars produced by shard->full marshaling, and vars consumed by
    # full->shard marshaling: @Sharding ops touching either are
    # shard_map plumbing, not constraints
    shard_to_full_outs = set()
    full_to_shard_ins = set()
    for line in lines:
        if "@SPMDShardToFullShape" in line:
            m = re.match(r"\s*(%[\w#.]+)\s*=", line)
            if m:
                shard_to_full_outs.add(m.group(1))
        if "@SPMDFullToShardShape" in line:
            for var in re.findall(r"@SPMDFullToShardShape\(([^)]*)\)",
                                  line):
                full_to_shard_ins.update(
                    v.strip() for v in var.split(","))
    out = []
    for i, line in enumerate(lines, 1):
        if "custom_call @Sharding" not in line:
            continue
        om = _SHARDING_OP_RE.search(line)
        if om is not None:
            result_var, operand_var = om.group(1), om.group(2)
            if result_var in full_to_shard_ins \
                    or operand_var in shard_to_full_outs:
                continue  # shard_map boundary marshaling
        sm = _SHARDING_ATTR_RE.search(line)
        # the RESULT type is the last tensor<> on the line
        tensors = _TENSOR_RE.findall(line)
        if sm and tensors:
            out.append((i, sm.group(1), tensors[-1]))
    return out


_ARG_SHARDING_RE = re.compile(
    r"(%arg\d+):\s*tensor<[^>]*>\s*\{[^}]*mhlo\.sharding\s*=\s*"
    r'"([^"]*)"')


def arg_shardings(text):
    """``[(lineno, arg_name, sharding_str)]`` for every entry-function
    argument carrying an ``mhlo.sharding`` annotation — the sharded
    roots the collective dataflow analysis walks from (the entry
    signature spans multiple lines on wide programs, so this scans
    every line rather than reparsing the balanced signature)."""
    out = []
    for i, line in enumerate(text.splitlines(), 1):
        if "mhlo.sharding" not in line:
            continue
        for m in _ARG_SHARDING_RE.finditer(line):
            out.append((i, m.group(1), m.group(2)))
    return out


_INTERLEAVE_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|reduce_scatter|all_gather|all_to_all)\b")
_INTERLEAVE_COMPUTE_RE = re.compile(
    r"stablehlo\.(dot_general|dot|convolution)\b")


def collective_compute_interleaving(text):
    """Module-order interleaving of collectives and heavy compute.

    StableHLO text preserves emission (trace) order, so an overlapped
    step — which launches each bucket's collective before tracing the
    earlier segments' backward — shows dot/convolution ops AFTER its
    first collective, while a sync-after-backward step's collectives
    form one trailing block. Returns ``{"collectives", "compute_ops",
    "compute_after_first_collective", "collectives_before_last_compute",
    "interleaved"}``; ``interleaved`` is True iff at least one
    collective precedes at least one compute op AND vice versa. A
    pre-scheduling heuristic (the scheduler may still reorder), used by
    the overlap tests/bench next to the ``overlap-serialization``
    dependence rule — order suggests, dependence proves."""
    coll, comp = [], []
    for i, line in enumerate(text.splitlines()):
        if _INTERLEAVE_COLLECTIVE_RE.search(line):
            coll.append(i)
        if _INTERLEAVE_COMPUTE_RE.search(line):
            comp.append(i)
    after = sum(1 for c in comp if coll and c > coll[0])
    before_last = sum(1 for c in coll if comp and c < comp[-1])
    return {
        "collectives": len(coll),
        "compute_ops": len(comp),
        "compute_after_first_collective": after,
        "collectives_before_last_compute": before_last,
        "interleaved": bool(after and before_last),
    }


def large_constant_bytes(text, min_bytes):
    """``[(lineno, nbytes, tensor_spec)]`` for ``stablehlo.constant``
    ops whose tensor type meets ``min_bytes`` — the text-level fallback
    for the trace-constant rule when no jaxpr is available. Splat
    constants (``dense<0.0e+00>``) are skipped: XLA materializes those
    lazily, they cost compile-time nothing."""
    out = []
    for i, line in enumerate(text.splitlines(), 1):
        s = line.strip()
        if not s.startswith(("%cst", "%c")) or "stablehlo.constant" not in s:
            continue
        m = re.search(r'dense<"', s)
        if m is None:
            continue  # splat or small inline literal
        tensors = _TENSOR_RE.findall(s)
        if not tensors:
            continue
        _, _, nbytes = parse_tensor_type(tensors[-1])
        if nbytes >= min_bytes:
            out.append((i, nbytes, tensors[-1]))
    return out


# -- optimised HLO (``compiled.as_text()``) ---------------------------------

_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\-.]+)\s.*\{\s*$")
_HLO_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w\-.]+)\s*=\s")
_HLO_OP_NAME_RE = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS_RE = re.compile(r"\bcalls=%([\w\-.]+)")
# what a fusion's time goes to, where it holds one: a matrix product (a
# dot is a convolution on the TPU) or a Mosaic kernel
_HLO_HEAVY_RE = re.compile(
    r' (?:convolution|dot)\(|custom_call_target="tpu_custom_call"')


def _commonest_stack(op_names):
    """Of a computation's ``op_name``s, the last one under the name stack
    (the path without its final primitive) that most of them share."""
    stacks = collections.Counter(n.rpartition("/")[0] for n in op_names)
    stack = stacks.most_common(1)[0][0]
    return next(n for n in reversed(op_names)
                if n.rpartition("/")[0] == stack)


def instruction_scopes(text):
    """``{instruction name: op_name}`` over every computation of an
    optimised HLO module (``jit(f).lower(...).compile().as_text()``).

    ``op_name`` is the jax name stack the instruction was traced under
    (``jit(step)/jvp(Model)/layer_0/mlp/dot_general``): flax module
    names, ``jax.named_scope`` and a Pallas kernel's ``name=`` all land
    there, and XLA keeps it through optimisation on the instruction that
    replaces the original. A fusion's own is its root's, which says
    little where XLA has fused work of several scopes (amp's unscale,
    the whole Adam update and the cast of the masters are one loop
    fusion whose root is the cast). So an instruction that ``calls=`` a
    computation answers for what is inside it: with the ``op_name`` of
    the matrix product or Mosaic kernel there if it holds one, since
    that is where its time goes, else with one from the name stack most
    of its instructions were traced under; with its own only where
    nothing inside has any. Any other instruction without an
    ``op_name`` (asynchronous ``copy-start``/``copy-done``, parameters
    XLA moved) is left out. Instruction names are unique in a module,
    and they are what a profile's ``XLA Ops`` events are named by."""
    scopes, calls, inside, heavy = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _HLO_INSTRUCTION_RE.match(line)
        if m is None:
            c = _HLO_COMPUTATION_RE.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        op = _HLO_OP_NAME_RE.search(line, m.end())
        if op is not None:
            scopes[name] = op.group(1)
            inside.setdefault(computation, []).append(op.group(1))
            if _HLO_HEAVY_RE.search(line, m.end()):
                heavy.setdefault(computation, op.group(1))
        called = _HLO_CALLS_RE.search(line, m.end())
        if called is not None:
            calls[name] = called.group(1)
    for name, called in calls.items():
        if called in heavy:
            scopes[name] = heavy[called]
        elif called in inside:
            scopes[name] = _commonest_stack(inside[called])
    return scopes
