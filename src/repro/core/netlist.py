"""Gate-level netlist IR for bespoke tree/forest circuits (DESIGN.md §10).

The one lowering every hardware artifact derives from: a tree (or forest)
plus a decoded chromosome — per-comparator precision and substituted integer
threshold — becomes an explicit netlist of 2-input printed gates:

  comparator cells  hard-wired ``X > t'`` chains, one AND2/OR2 per significant
                    bit above the lowest set bit of ``t' + 1`` — the SAME
                    construction `core.area.comparator_gate_counts` prices, so
                    gate counts and the area LUT cannot drift apart;
  path-AND cells    one AND tree per leaf over comparator literals;
  class-OR cells    per-class one-hot vote wires (OR of the class's leaves);
  vote adders       forests only: a popcount adder tree per class — §2's vote
                    matmul in hardware — plus an argmax comparator chain with
                    first-max tie-breaking (matching `jnp.argmax`).

Construction is hash-consed (structural CSE, like DC synthesis of the flat
bespoke netlist: identical comparators — within or across trees — share
hardware) with constant propagation (a ``t' = 2^p - 1`` comparator folds to
constant false and its dead path logic vanishes). From the finished
`Circuit`:

  - `simulate(circuit, x8)` evaluates the whole test set in one vectorized
    numpy pass on the host (gates grouped by logic level, one gather/op per
    level) — the hardware oracle `core.rtl` emission is
    verified against;
  - `gate_counts(circuit)` / `netlist_area_mm2(circuit)` give the
    synthesized-netlist "actual" area the GA's additive-LUT estimate is
    measured against (the paper's Fig. 5 estimated-vs-actual gap).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import area as area_mod
from repro.core.tree import ParallelTree

# gate opcodes; CONST0/CONST1 are always gates 0 and 1 of every netlist
CONST0, CONST1, INPUT, NOT, AND, OR, XOR = range(7)
OP_NAMES = ("const0", "const1", "input", "not", "and", "or", "xor")
MASTER_BITS = 8


class NetlistBuilder:
    """Hash-consed gate builder with constant folding.

    Gate ids are topologically ordered by construction (operands always
    precede their gate), so a single linear pass levelizes the netlist.
    """

    def __init__(self):
        self.op: list[int] = []
        self.a: list[int] = []
        self.b: list[int] = []
        self._cache: dict[tuple[int, int, int], int] = {}
        self.zero = self._raw(CONST0, -1, -1)   # gate 0
        self.one = self._raw(CONST1, -1, -1)    # gate 1

    def _raw(self, op: int, a: int, b: int) -> int:
        key = (op, a, b)
        gid = self._cache.get(key)
        if gid is None:
            gid = len(self.op)
            self.op.append(op)
            self.a.append(a)
            self.b.append(b)
            self._cache[key] = gid
        return gid

    # -- primitives with folding -------------------------------------------
    def input_bit(self, feature: int, bit: int) -> int:
        """Bit `bit` (LSB = 0) of feature `feature`'s 8-bit master code."""
        return self._raw(INPUT, int(feature), int(bit))

    def not_(self, x: int) -> int:
        if x == self.zero:
            return self.one
        if x == self.one:
            return self.zero
        if self.op[x] == NOT:           # ~~x = x
            return self.a[x]
        return self._raw(NOT, x, -1)

    def _is_complement(self, x: int, y: int) -> bool:
        return (self.op[y] == NOT and self.a[y] == x) or (
            self.op[x] == NOT and self.a[x] == y)

    def and_(self, x: int, y: int) -> int:
        if x == y:
            return x
        if x == self.zero or y == self.zero:
            return self.zero
        if x == self.one:
            return y
        if y == self.one:
            return x
        if self._is_complement(x, y):
            return self.zero
        if x > y:                       # commutative normal form
            x, y = y, x
        return self._raw(AND, x, y)

    def or_(self, x: int, y: int) -> int:
        if x == y:
            return x
        if x == self.one or y == self.one:
            return self.one
        if x == self.zero:
            return y
        if y == self.zero:
            return x
        if self._is_complement(x, y):
            return self.one
        if x > y:
            x, y = y, x
        return self._raw(OR, x, y)

    def xor_(self, x: int, y: int) -> int:
        if x == y:
            return self.zero
        if x == self.zero:
            return y
        if y == self.zero:
            return x
        if x == self.one:
            return self.not_(y)
        if y == self.one:
            return self.not_(x)
        if self._is_complement(x, y):
            return self.one
        if x > y:
            x, y = y, x
        return self._raw(XOR, x, y)

    def _reduce(self, wires: list[int], fn) -> int:
        """Balanced binary reduction (minimizes logic depth/sim levels)."""
        if not wires:
            raise ValueError("empty reduction")
        while len(wires) > 1:
            nxt = [fn(wires[i], wires[i + 1])
                   for i in range(0, len(wires) - 1, 2)]
            if len(wires) % 2:
                nxt.append(wires[-1])
            wires = nxt
        return wires[0]

    def and_many(self, wires: list[int]) -> int:
        return self._reduce(list(wires), self.and_) if wires else self.one

    def or_many(self, wires: list[int]) -> int:
        return self._reduce(list(wires), self.or_) if wires else self.zero

    # -- comparator lowering (mirrors core.area.comparator_gate_counts) ----
    def comparator(self, feature: int, t_int: int, p: int) -> int:
        """Hard-wired ``X > t'`` where X is the top `p` master-code bits.

        ``X > t  ==  X >= u`` with ``u = t + 1``; scanning u from the LSB,
        the lowest set bit j contributes ``g = X_j`` for free, and every
        higher bit exactly one gate (u_i = 1 -> AND, u_i = 0 -> OR) — the
        same count `core.area.comparator_gate_counts` prices. ``u = 2^p``
        (t' = 2^p - 1) is constant false."""
        u = int(t_int) + 1
        if u >= (1 << p):
            return self.zero
        tz = (u & -u).bit_length() - 1          # trailing zeros of u
        # truncated bit j of X is master bit (8 - p + j)
        g = self.input_bit(feature, MASTER_BITS - p + tz)
        for i in range(tz + 1, p):
            xi = self.input_bit(feature, MASTER_BITS - p + i)
            g = self.and_(xi, g) if (u >> i) & 1 else self.or_(xi, g)
        return g

    # -- arithmetic (vote adder tree + argmax chain) -----------------------
    def full_add(self, x: int, y: int, c: int) -> tuple[int, int]:
        s1 = self.xor_(x, y)
        return self.xor_(s1, c), self.or_(self.and_(x, y), self.and_(s1, c))

    def add(self, a_bits: list[int], b_bits: list[int]) -> list[int]:
        """Ripple-carry add of LSB-first vectors; result carries the overflow
        bit, so popcounts never wrap."""
        n = max(len(a_bits), len(b_bits))
        a_bits = list(a_bits) + [self.zero] * (n - len(a_bits))
        b_bits = list(b_bits) + [self.zero] * (n - len(b_bits))
        out, carry = [], self.zero
        for x, y in zip(a_bits, b_bits):
            s, carry = self.full_add(x, y, carry)
            out.append(s)
        out.append(carry)
        return out

    def popcount(self, wires: list[int]) -> list[int]:
        """LSB-first bit-vector count of set wires (balanced adder tree)."""
        if not wires:
            return [self.zero]
        vecs = [[w] for w in wires]
        while len(vecs) > 1:
            nxt = [self.add(vecs[i], vecs[i + 1])
                   for i in range(0, len(vecs) - 1, 2)]
            if len(vecs) % 2:
                nxt.append(vecs[-1])
            vecs = nxt
        return vecs[0]

    def gt(self, a_bits: list[int], b_bits: list[int]) -> int:
        """Unsigned a > b over LSB-first vectors."""
        n = max(len(a_bits), len(b_bits))
        a_bits = list(a_bits) + [self.zero] * (n - len(a_bits))
        b_bits = list(b_bits) + [self.zero] * (n - len(b_bits))
        g = self.zero
        for x, y in zip(a_bits, b_bits):        # LSB -> MSB
            gt_i = self.and_(x, self.not_(y))
            eq_i = self.not_(self.xor_(x, y))
            g = self.or_(gt_i, self.and_(eq_i, g))
        return g

    def mux_vec(self, sel: int, a_bits: list[int],
                b_bits: list[int]) -> list[int]:
        """sel ? a : b, bitwise; vectors padded to equal width."""
        n = max(len(a_bits), len(b_bits))
        a_bits = list(a_bits) + [self.zero] * (n - len(a_bits))
        b_bits = list(b_bits) + [self.zero] * (n - len(b_bits))
        ns = self.not_(sel)
        return [self.or_(self.and_(sel, x), self.and_(ns, y))
                for x, y in zip(a_bits, b_bits)]

    def const_vec(self, value: int, width: int) -> list[int]:
        return [self.one if (value >> i) & 1 else self.zero
                for i in range(width)]

    def sub(self, a_bits: list[int], b_bits: list[int]) -> list[int]:
        """Unsigned a - b over LSB-first vectors as ``a + ~b + 1``.

        Valid (wrap-free) only when a >= b; callers mask the result behind a
        `gt`/`mux_vec` select so the wrapped case is never observed — the
        printed-MLP ReLU cell does exactly that (DESIGN.md §15)."""
        n = max(len(a_bits), len(b_bits))
        a_bits = list(a_bits) + [self.zero] * (n - len(a_bits))
        b_bits = list(b_bits) + [self.zero] * (n - len(b_bits))
        out, carry = [], self.one          # +1 of the two's complement
        for x, y in zip(a_bits, b_bits):
            s, carry = self.full_add(x, self.not_(y), carry)
            out.append(s)
        return out                          # final carry dropped (a >= b)

    def sum_vecs(self, vecs: list) -> list[int]:
        """Balanced adder tree over LSB-first bit-vectors (MAC accumulate)."""
        if not vecs:
            return [self.zero]
        vecs = [list(v) for v in vecs]
        while len(vecs) > 1:
            nxt = [self.add(vecs[i], vecs[i + 1])
                   for i in range(0, len(vecs) - 1, 2)]
            if len(vecs) % 2:
                nxt.append(vecs[-1])
            vecs = nxt
        return vecs[0]


# ---------------------------------------------------------------------------
# cells: the structure `core.rtl` prints and the simulator verifies
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ComparatorCell:
    """One lowered comparator. `bits`/`t_int` are the EFFECTIVE width and
    substituted threshold the hardware implements — for a k-LSB-truncated
    cell (DESIGN.md §16) that is (p - k, t' >> k); `trunc` records k for
    provenance. `core.rtl` prints cells verbatim, so emitted Verilog is
    always the effective (truncated) comparator."""

    feature: int
    bits: int
    t_int: int      # SUBSTITUTED integer threshold t' (effective)
    wire: int       # == 0 (CONST0) when t' = 2^p - 1 folds the cell away
    trunc: int = 0  # LSB stages dropped from the requested-width cell


@dataclasses.dataclass
class LeafCell:
    literals: list  # [(comparator index, positive: bool), ...]
    leaf_class: int
    wire: int


@dataclasses.dataclass
class TreeCells:
    comparators: list  # [ComparatorCell]
    leaves: list       # [LeafCell]
    votes: list        # per-class one-hot vote wires (OR of own leaves)


@dataclasses.dataclass
class Circuit:
    """A finished netlist: frozen gate arrays + the cell structure."""

    op: np.ndarray        # int8[G]
    a: np.ndarray         # int32[G]
    b: np.ndarray         # int32[G]
    out_bits: tuple       # class-index wires, LSB first
    trees: list           # [TreeCells]
    n_classes: int

    @property
    def n_gates(self) -> int:
        return int(self.op.shape[0])

    @property
    def n_trees(self) -> int:
        return len(self.trees)


def class_bits(n_classes: int) -> int:
    return max(1, int(np.ceil(np.log2(max(n_classes, 2)))))


def build_tree_cells(nb: NetlistBuilder, pt: ParallelTree, bits, t_int,
                     n_classes: int, trunc=None) -> TreeCells:
    """Lower one tree's comparators/leaves/votes into the shared builder.

    `trunc` (optional, per-comparator int array) drops the k lowest stages
    of each comparator chain (DESIGN.md §16): the cell lowered is the exact
    comparator at width `bits - k` against `t_int >> k` — the construction
    `core.area.trunc_comparator_gate_counts` prices, so truncated gate
    counts and the area LUT cannot drift apart either.
    """
    bits = np.asarray(bits)
    t_int = np.asarray(t_int)
    trunc = (np.zeros_like(bits) if trunc is None else np.asarray(trunc))
    comps = []
    for c in range(pt.n_comparators):
        k = int(trunc[c])
        p_eff = max(int(bits[c]) - k, 0)
        t_eff = int(t_int[c]) >> k
        comps.append(ComparatorCell(
            int(pt.feature[c]), p_eff, t_eff,
            nb.comparator(int(pt.feature[c]), t_eff, p_eff), trunc=k))
    leaves = []
    for l in range(pt.n_leaves):
        lits = [(c, int(pt.path[l, c]) == 1)
                for c in range(pt.n_comparators) if int(pt.path[l, c]) != 0]
        wire = nb.and_many(
            [comps[c].wire if pos else nb.not_(comps[c].wire)
             for c, pos in lits])
        leaves.append(LeafCell(lits, int(pt.leaf_class[l]), wire))
    votes = [nb.or_many([lf.wire for lf in leaves if lf.leaf_class == c])
             for c in range(n_classes)]
    return TreeCells(comps, leaves, votes)


def build_circuit(ptrees, bits, t_int, n_classes: int, trunc=None,
                  vote_adder: str = "exact") -> Circuit:
    """Tree/forest + decoded chromosome -> verified-hardware netlist.

    `bits`/`t_int` are concatenated per-comparator arrays across the K trees
    (the `SearchProblem` chromosome layout); `trunc` optionally truncates
    each comparator's k lowest stages (DESIGN.md §16). K = 1 skips the vote
    adders: the one-hot votes binary-encode directly (exactly one leaf
    fires), and `vote_adder` is inert. K > 1 builds the vote stage selected
    by `vote_adder`:

      "exact"  per-class popcount adder tree — majority vote;
      "approx" per-class saturating OR-tree (1-bit "did ANY tree vote c"),
               the cross-layer paper's approximate vote adder.

    Either way the argmax comparator chain keeps first-max tie-breaking —
    bit-identical to `predict_votes`' `jnp.argmax` over (possibly
    saturated) vote counts.
    """
    if vote_adder not in ("exact", "approx"):
        raise ValueError(f"unknown vote_adder {vote_adder!r}")
    if isinstance(ptrees, ParallelTree):
        ptrees = [ptrees]
    bits = np.asarray(bits)
    t_int = np.asarray(t_int)
    trunc = (np.zeros_like(bits) if trunc is None else np.asarray(trunc))
    nb = NetlistBuilder()
    trees, off = [], 0
    for pt in ptrees:
        n = pt.n_comparators
        trees.append(build_tree_cells(nb, pt, bits[off:off + n],
                                      t_int[off:off + n], n_classes,
                                      trunc=trunc[off:off + n]))
        off += n
    if off != bits.shape[0]:
        raise ValueError(
            f"chromosome covers {bits.shape[0]} comparators, trees have {off}")

    n_bits = class_bits(n_classes)
    if len(trees) == 1:
        # one-hot votes -> binary class index (exactly one leaf fires)
        out = [nb.or_many([trees[0].votes[c] for c in range(n_classes)
                           if (c >> b) & 1]) for b in range(n_bits)]
    else:
        out = _vote_argmax(nb, trees, n_classes, approx=vote_adder == "approx")
    return Circuit(
        op=np.asarray(nb.op, np.int8),
        a=np.asarray(nb.a, np.int32),
        b=np.asarray(nb.b, np.int32),
        out_bits=tuple(out[:n_bits]),
        trees=trees,
        n_classes=int(n_classes),
    )


def _vote_argmax(nb: NetlistBuilder, trees, n_classes: int,
                 approx: bool) -> list:
    """Forest vote stage: per-class counts + first-max argmax chain.

    Exact mode counts votes with popcount adder trees; approx mode
    saturates each class to the 1-bit OR of its votes (DESIGN.md §16) —
    the argmax chain is shared, operating on 1-bit "counts"."""
    n_bits = class_bits(n_classes)
    if approx:
        counts = [[nb.or_many([t.votes[c] for t in trees])]
                  for c in range(n_classes)]
    else:
        counts = [nb.popcount([t.votes[c] for t in trees])
                  for c in range(n_classes)]
    best_cnt, best_idx = counts[0], nb.const_vec(0, n_bits)
    for c in range(1, n_classes):
        sel = nb.gt(counts[c], best_cnt)
        best_cnt = nb.mux_vec(sel, counts[c], best_cnt)
        best_idx = nb.mux_vec(sel, nb.const_vec(c, n_bits), best_idx)
    return best_idx


def vote_adder_gate_counts(n_trees: int, n_classes: int,
                           approx: bool) -> tuple[int, int, int, int]:
    """(n_and, n_or, n_not, n_xor) of an ISOLATED forest vote stage.

    Builds the vote stage on free-standing input wires (one per
    tree x class) and inventories its gates — the number `core.area.
    vote_adder_units` prices, so the GA's vote-adder area quanta come from
    the same lowering `build_circuit` emits. An isolated stage can't share
    logic with tree cells, so (like the additive comparator LUT) this is
    the pre-CSE estimate the netlist "actual" area is measured against.
    """
    nb = NetlistBuilder()
    trees = [TreeCells([], [], [nb.input_bit(k, c) for c in range(n_classes)])
             for k in range(n_trees)]
    _vote_argmax(nb, trees, n_classes, approx=approx)
    op = np.asarray(nb.op)
    return (int((op == AND).sum()), int((op == OR).sum()),
            int((op == NOT).sum()), int((op == XOR).sum()))


# ---------------------------------------------------------------------------
# printed-MLP cells (DESIGN.md §15): MAC rows + ReLU + signed argmax
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MacNeuronCell:
    """One integer-weight neuron: shifted-copy MAC rows + an activation cell.

    The signed accumulator is kept as an unsigned (pos, neg) pair — positive
    and negative MAC contributions summed separately — so no sign bit ever
    exists in hardware: ReLU is ``pos > neg ? pos - neg : 0`` and the output
    argmax compares ``pos_c + neg_best`` against ``pos_best + neg_c``."""

    weights: list       # effective signed integer weights, one per input
    relu: bool          # hidden neurons apply ReLU + the static right shift
    pos: list           # unsigned positive-sum wires, LSB first
    neg: list           # unsigned negative-sum wires, LSB first
    out: list           # activation output wires (ReLU'd + shifted), LSB first


@dataclasses.dataclass
class MlpCells:
    hidden: list        # [MacNeuronCell], ReLU outputs feed the next layer
    outputs: list       # [MacNeuronCell], (pos, neg) pairs feed the argmax
    shift: int          # static right shift applied after every ReLU


def _mac_rows(nb: NetlistBuilder, in_vecs, weights):
    """Split a neuron's MAC terms into (positive, negative) shifted-copy rows.

    Each set bit s of |w| contributes the input vector shifted left by s
    (free wire: s leading CONST0s); the sign of w routes the row to the
    positive or negative accumulator."""
    pos, neg = [], []
    for vec, w in zip(in_vecs, weights):
        w = int(w)
        if w == 0:
            continue
        dst = pos if w > 0 else neg
        mag, s = abs(w), 0
        while mag:
            if mag & 1:
                dst.append([nb.zero] * s + list(vec))
            mag >>= 1
            s += 1
    return pos, neg


def build_mac_neuron(nb: NetlistBuilder, in_vecs, weights, *,
                     relu: bool, shift: int = 0) -> MacNeuronCell:
    """Lower one integer-weight neuron into the shared builder."""
    pos_rows, neg_rows = _mac_rows(nb, in_vecs, weights)
    pos = nb.sum_vecs(pos_rows)
    neg = nb.sum_vecs(neg_rows)
    out = []
    if relu:
        # ReLU: pos > neg ? pos - neg : 0; `sub` wraps when pos < neg but the
        # mux masks that case. The static right shift is free wire (bit drop).
        sel = nb.gt(pos, neg)
        diff = nb.mux_vec(sel, nb.sub(pos, neg), [nb.zero] * max(len(pos), len(neg)))
        out = diff[shift:] if shift < len(diff) else [nb.zero]
    return MacNeuronCell(list(int(w) for w in weights), relu, pos, neg, out)


def build_mlp_circuit(w1, w2, shift: int, n_classes: int) -> Circuit:
    """Integer-weight MLP (one hidden ReLU layer) -> verified netlist.

    `w1` (F, H) and `w2` (H, C) are EFFECTIVE signed integer weight codes
    (post-snap, rescaled to the master grid); `shift` is the static right
    shift applied to every ReLU output. Inputs are the 8-bit master codes.
    The argmax chain keeps first-max tie semantics (matching `jnp.argmax`)
    by replacing the incumbent only on strict greater-than, scanning classes
    in ascending order. Bit-exact against the tensor forward pass because
    both sides compute exact integer arithmetic (DESIGN.md §15).
    """
    w1 = np.asarray(w1)
    w2 = np.asarray(w2)
    n_features, n_hidden = w1.shape
    if w2.shape != (n_hidden, n_classes):
        raise ValueError(f"w2 shape {w2.shape} != ({n_hidden}, {n_classes})")
    nb = NetlistBuilder()
    in_vecs = [[nb.input_bit(f, i) for i in range(MASTER_BITS)]
               for f in range(n_features)]
    hidden = [build_mac_neuron(nb, in_vecs, w1[:, j], relu=True, shift=shift)
              for j in range(n_hidden)]
    h_vecs = [cell.out for cell in hidden]
    outputs = [build_mac_neuron(nb, h_vecs, w2[:, c], relu=False)
               for c in range(n_classes)]

    n_bits = class_bits(n_classes)
    best_pos, best_neg = outputs[0].pos, outputs[0].neg
    best_idx = nb.const_vec(0, n_bits)
    for c in range(1, n_classes):
        # s_c > s_best  <=>  pos_c + neg_best > pos_best + neg_c  (unsigned)
        sel = nb.gt(nb.add(outputs[c].pos, best_neg),
                    nb.add(best_pos, outputs[c].neg))
        best_pos = nb.mux_vec(sel, outputs[c].pos, best_pos)
        best_neg = nb.mux_vec(sel, outputs[c].neg, best_neg)
        best_idx = nb.mux_vec(sel, nb.const_vec(c, n_bits), best_idx)
    return Circuit(
        op=np.asarray(nb.op, np.int8),
        a=np.asarray(nb.a, np.int32),
        b=np.asarray(nb.b, np.int32),
        out_bits=tuple(best_idx[:n_bits]),
        trees=[MlpCells(hidden, outputs, int(shift))],
        n_classes=int(n_classes),
    )


# ---------------------------------------------------------------------------
# batched simulation — the hardware oracle
# ---------------------------------------------------------------------------

def levelize(circuit: Circuit) -> np.ndarray:
    """(G,) int32 logic level per gate (0 = inputs/constants).

    Gate ids are topologically ordered by construction, so one linear pass
    suffices. Shared by `simulate` and the fault-injection simulator
    (`core.faults`, DESIGN.md §17), which applies stuck-at overrides as
    per-level masks on the same schedule.
    """
    op, a, b = circuit.op, circuit.a, circuit.b
    level = np.zeros(circuit.n_gates, np.int32)
    for i in np.flatnonzero(op >= NOT):
        la = level[a[i]]
        lb = level[b[i]] if op[i] != NOT else 0
        level[i] = max(la, lb) + 1
    return level


def simulate(circuit: Circuit, x8) -> np.ndarray:
    """(B,) int32 predicted class over (B, F) int master codes.

    One vectorized host pass in numpy, no `lax.scan`: gates are grouped by
    logic level (operands always precede gates, so one linear pass
    levelizes), and each level is a single gather + boolean op over all its
    gates at once. Numpy, not jnp: every level has its own gather shape, so
    op-by-op jnp would compile (and, on an accelerator, dispatch) per level
    and per batch size. Bit-exact against `search.problem.predict_votes` by
    construction — asserted per pareto point by the engine's `--verify-rtl`
    path.
    """
    op, a, b = circuit.op, circuit.a, circuit.b
    g = circuit.n_gates
    logic = op >= NOT
    level = levelize(circuit)

    x8 = np.asarray(x8, np.int32)
    n_b = x8.shape[0]
    vals = np.zeros((n_b, g), bool)

    base = np.flatnonzero(level == 0)
    feat = np.maximum(a[base], 0)
    bit = np.maximum(b[base], 0)
    in_vals = ((x8[:, feat] >> bit[None, :]) & 1).astype(bool)
    base_ops = op[base][None, :]
    vals[:, base] = np.where(base_ops == INPUT, in_vals, base_ops == CONST1)

    for lvl in range(1, int(level.max()) + 1 if logic.any() else 1):
        idx = np.flatnonzero(level == lvl)
        if idx.size == 0:
            continue
        av = vals[:, a[idx]]
        bv = vals[:, np.maximum(b[idx], 0)]
        ops = op[idx][None, :]
        vals[:, idx] = np.where(
            ops == NOT, ~av,
            np.where(ops == AND, av & bv,
                     np.where(ops == OR, av | bv, av ^ bv)))

    cls = np.zeros((n_b,), np.int32)
    for i, w in enumerate(circuit.out_bits):
        cls |= vals[:, w].astype(np.int32) << i
    return cls


# ---------------------------------------------------------------------------
# measured area — the estimated-vs-actual artifact
# ---------------------------------------------------------------------------

def gate_counts(circuit: Circuit) -> dict:
    """Logic-gate inventory after CSE/constant propagation."""
    ops, counts = np.unique(circuit.op, return_counts=True)
    by_name = {OP_NAMES[o]: int(c) for o, c in zip(ops, counts)}
    return {name: by_name.get(name, 0) for name in ("and", "or", "not", "xor")}


def netlist_area_mm2(circuit: Circuit) -> float:
    """Synthesized-netlist area: every gate priced, nothing estimated.

    This is the framework's "actual" oracle standing in for the paper's DC
    measurements; compare against the GA's additive-LUT estimate
    (`search.problem.chromosome_area_mm2`) for the Fig. 5 gap."""
    c = gate_counts(circuit)
    return area_mod.gate_area_mm2(n_and=c["and"], n_or=c["or"],
                                  n_not=c["not"], n_xor=c["xor"])
