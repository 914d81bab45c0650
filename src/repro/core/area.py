"""Bespoke-comparator area model + Area LUT (paper Fig. 4) + power model.

The container has no Synopsys DC / EGT PDK, so the LUT is produced by an
exact gate count of the constant-propagated comparator netlist, calibrated to
the paper's published magnitudes (Table I / Fig. 4). See DESIGN.md §4.

Hard-wired unsigned greater-than:  X > t  ==  X >= u with u = t + 1.
Scanning u from the LSB, with g initially "true":
  - bits below the lowest set bit of u are free (g stays true),
  - the lowest set bit j gives g = X_j (free),
  - every higher bit adds exactly one 2-input gate:
      u_i = 1 -> g = X_i AND g      (AND2)
      u_i = 0 -> g = X_i OR  g      (OR2)
  - u = 2^p (t = 2^p - 1) is constant-false: zero gates.

So gates(t, p) = p - 1 - tz(t + 1)   (tz = count of trailing zeros), split
into ANDs/ORs by the bit pattern — non-linear in t with valleys at
t = 2^k - 1 and a sawtooth over odd/even t, matching the character of the
paper's Fig. 4. No inverters are ever needed for a constant comparison in
this form.

EGT calibration (printed gates are *large*):
  AREA_AND2 / AREA_OR2 are per-gate areas in mm^2; NODE/LEAF overheads model
  the leaf-decode + class-mux logic the paper synthesizes around the
  comparators. POWER_PER_MM2 is the slope that reproduces every row of the
  paper's Table I within ~5% (7.55/162.50 = 0.0465 ... 25.0/574.46 = 0.0435).
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core.quant import MAX_BITS, MIN_BITS

# --- EGT PDK calibration constants (see DESIGN.md §4 and benchmarks) --------
# Fitted against paper Table I with the unique-comparator (CSE) model:
# printed EGT 2-input gates are ~0.56 mm^2; per-node overheads are tiny once
# sharing is accounted for (benchmarks/paper_tables.py::calibration).
AREA_AND2_MM2 = 0.55     # printed EGT 2-input gate
AREA_OR2_MM2 = 0.57
AREA_NOT_MM2 = 0.28      # inverter: ~half a 2-input EGT gate
AREA_XOR2_MM2 = 0.83     # 2-input XOR: ~1.5x AND2 (vote adders, DESIGN.md §10)

# Every gate area above is an integer multiple of this quantum, so a
# comparator area is an exact integer number of quanta. The sweep engine
# (DESIGN.md §11) scores area by summing *integer* quanta in f32 — exact for
# any reduction order/tiling as long as the total stays < 2^24 quanta
# (167 m^2 of circuit) — and scales once at the end, which is what makes the
# vmapped multi-problem fitness bit-identical to the serial loop.
AREA_QUANTUM_MM2 = 0.01
_AND2_UNITS = round(AREA_AND2_MM2 / AREA_QUANTUM_MM2)
_OR2_UNITS = round(AREA_OR2_MM2 / AREA_QUANTUM_MM2)
assert abs(_AND2_UNITS * AREA_QUANTUM_MM2 - AREA_AND2_MM2) < 1e-12
assert abs(_OR2_UNITS * AREA_QUANTUM_MM2 - AREA_OR2_MM2) < 1e-12
NODE_OVERHEAD_MM2 = 0.02  # per internal node: routing + decision buffering
LEAF_OVERHEAD_MM2 = 0.04  # per leaf: path-AND + class mux contribution
POWER_PER_MM2_MW = 0.0455  # paper Table I slope (mW per mm^2)
DELAY_BASE_MS = 19.2       # paper Table I affine fit (reported for completeness)
DELAY_PER_COMP_MS = 0.11


def comparator_gate_counts(t: int, p: int) -> tuple[int, int]:
    """(n_and2, n_or2) for hard-wired ``X > t`` with p-bit unsigned X."""
    u = t + 1
    if u >= (1 << p):
        return 0, 0
    tz = (u & -u).bit_length() - 1  # trailing zeros
    n_and = bin(u >> (tz + 1)).count("1")            # set bits above lowest
    n_or = (p - 1 - tz) - n_and                      # clear bits above lowest
    return n_and, n_or


def comparator_area_mm2(t: int, p: int) -> float:
    n_and, n_or = comparator_gate_counts(t, p)
    return n_and * AREA_AND2_MM2 + n_or * AREA_OR2_MM2


def trunc_comparator_gate_counts(t: int, p: int, k: int) -> tuple[int, int]:
    """(n_and2, n_or2) for a k-LSB-truncated p-bit comparator (DESIGN.md §16).

    Dropping the k lowest stages of the hard-wired ``X > t`` chain leaves
    exactly the exact comparator of width p - k against threshold t >> k —
    so truncated cells are priced (and lowered) with the same primitives.
    Width p - k <= 0 degenerates to constant false: zero gates.
    """
    if k >= p:
        return 0, 0
    return comparator_gate_counts(t >> k, p - k)


def trunc_comparator_area_mm2(t: int, p: int, k: int) -> float:
    n_and, n_or = trunc_comparator_gate_counts(t, p, k)
    return n_and * AREA_AND2_MM2 + n_or * AREA_OR2_MM2


def build_area_lut() -> tuple[np.ndarray, np.ndarray]:
    """Exhaustive LUT over p in [0, MAX_BITS], t in [0, 2^p).

    Returns (lut, offsets):
      lut: float32[sum 2^p] of comparator areas (mm^2)
      offsets: int32[MAX_BITS+1], LUT row start per precision; entry for
               precision p is lut[offsets[p] + t].

    Rows below MIN_BITS exist because LSB truncation (DESIGN.md §16) shrinks
    a comparator's *effective* width down to MIN_BITS - MAX_TRUNC (= 0, the
    constant-false comparator); those rows are all-zero (a 0/1-bit unsigned
    greater-than needs no gates) but must occupy distinct offsets so
    `offsets[p_eff] + t_eff` never aliases a wider row.
    """
    offsets = np.zeros(MAX_BITS + 1, dtype=np.int32)
    chunks = []
    pos = 0
    for p in range(0, MAX_BITS + 1):
        offsets[p] = pos
        row = np.array(
            [comparator_area_mm2(t, p) for t in range(1 << p)], dtype=np.float32
        )
        chunks.append(row)
        pos += 1 << p
    return np.concatenate(chunks).astype(np.float32), offsets


def comparator_area_units(t: int, p: int) -> int:
    """Comparator area as an exact integer count of AREA_QUANTUM_MM2 quanta."""
    n_and, n_or = comparator_gate_counts(t, p)
    return n_and * _AND2_UNITS + n_or * _OR2_UNITS


def build_area_unit_lut() -> tuple[np.ndarray, np.ndarray]:
    """Integer-quanta twin of `build_area_lut` (same indexing scheme).

    Entries are small integers stored as f32 (exactly representable), so a
    masked/padded population sum of LUT rows is bit-identical under any
    reduction order — the property the vmapped sweep fitness relies on
    (DESIGN.md §11). `lut_units * AREA_QUANTUM_MM2` recovers mm^2.
    """
    offsets = np.zeros(MAX_BITS + 1, dtype=np.int32)
    chunks = []
    pos = 0
    for p in range(0, MAX_BITS + 1):
        offsets[p] = pos
        row = np.array([comparator_area_units(t, p) for t in range(1 << p)],
                       dtype=np.float32)
        chunks.append(row)
        pos += 1 << p
    return np.concatenate(chunks), offsets


# --- forest vote-adder cells (DESIGN.md §16) --------------------------------
# The vote stage of a K-tree forest is priced from the SAME netlist the
# hardware lowers to: an isolated vote-stage harness (popcount + argmax chain
# for the exact adder, saturating OR-tree + 1-bit argmax for the approximate
# one) is built once per (n_trees, n_classes, mode) and its gate inventory
# converted to exact integer quanta. Deferred import breaks the
# netlist -> area module cycle; lru_cache makes repeat pricing free.


@functools.lru_cache(maxsize=None)
def vote_adder_units(n_trees: int, n_classes: int, approx: bool) -> int:
    """Vote-adder area as exact integer AREA_QUANTUM_MM2 quanta.

    Zero for single-tree designs (K = 1 encodes the winning class directly,
    no adder exists in either mode — the vote gene is inert there)."""
    if n_trees <= 1:
        return 0
    from repro.core import netlist
    counts = netlist.vote_adder_gate_counts(n_trees, n_classes, approx=approx)
    units = gate_area_mm2(*counts) / AREA_QUANTUM_MM2
    iunits = round(units)
    assert abs(iunits - units) < 1e-6
    return iunits


# --- printed-MLP MAC / activation cells (DESIGN.md §15) ---------------------
# A MAC term is lowered as shifted-copy rows through ripple full adders (the
# §10 `full_add` cell: 2 XOR2 + 2 AND2 + 1 OR2); a negative weight costs one
# extra adder row (two's-complement add of the inverted operand). The
# activation cell (ReLU / argmax compare leg) is priced per accumulator bit:
# one compare stage (XOR2 + 2 AND2 + OR2 + NOT) per bit. All constants are
# integer multiples of AREA_QUANTUM_MM2, so MLP areas sum in exact integer
# quanta exactly like comparator areas — the property the vmapped sweep
# fitness relies on (DESIGN.md §11).
AREA_FA_MM2 = 2 * AREA_XOR2_MM2 + 2 * AREA_AND2_MM2 + AREA_OR2_MM2
AREA_ACT_BIT_MM2 = AREA_XOR2_MM2 + 2 * AREA_AND2_MM2 + AREA_OR2_MM2 + AREA_NOT_MM2
_FA_UNITS = round(AREA_FA_MM2 / AREA_QUANTUM_MM2)
_ACT_BIT_UNITS = round(AREA_ACT_BIT_MM2 / AREA_QUANTUM_MM2)
assert abs(_FA_UNITS * AREA_QUANTUM_MM2 - AREA_FA_MM2) < 1e-9
assert abs(_ACT_BIT_UNITS * AREA_QUANTUM_MM2 - AREA_ACT_BIT_MM2) < 1e-9


def mac_area_units(code: int, in_bits: int) -> int:
    """One integer-weight MAC term as exact AREA_QUANTUM_MM2 quanta.

    `code` is the effective signed weight; each set bit of |code| is one
    shifted-copy adder row of `in_bits` full adders, and a negative weight
    adds one subtractor row. A zero weight is free wire."""
    c = int(code)
    if c == 0:
        return 0
    rows = bin(abs(c)).count("1") + (1 if c < 0 else 0)
    return rows * int(in_bits) * _FA_UNITS


def mac_area_mm2(code: int, in_bits: int) -> float:
    return mac_area_units(code, in_bits) * AREA_QUANTUM_MM2


def act_area_units(acc_bits: int) -> int:
    """Activation cell (ReLU zero-mux or argmax compare leg) in quanta."""
    return int(acc_bits) * _ACT_BIT_UNITS


def act_area_mm2(acc_bits: int) -> float:
    return act_area_units(acc_bits) * AREA_QUANTUM_MM2


def mlp_neuron_area_units(codes, in_bits: int, acc_bits: int) -> int:
    """Area of one printed-MLP neuron: its MAC terms + one activation cell."""
    import numpy as np
    codes = np.asarray(codes).ravel()
    return (sum(mac_area_units(int(c), in_bits) for c in codes.tolist())
            + act_area_units(acc_bits))


def gate_area_mm2(n_and: int = 0, n_or: int = 0, n_not: int = 0,
                  n_xor: int = 0) -> float:
    """Area of an explicit gate inventory (the netlist oracle, DESIGN.md §10).

    Unlike the additive LUT estimate, this prices EVERY gate the circuit
    actually contains — comparators after CSE, path-AND inverters, and the
    forest vote adder/argmax logic the LUT models only as per-node/leaf
    overheads."""
    return (n_and * AREA_AND2_MM2 + n_or * AREA_OR2_MM2
            + n_not * AREA_NOT_MM2 + n_xor * AREA_XOR2_MM2)


def tree_overhead_mm2(n_comparators: int, n_leaves: int) -> float:
    return n_comparators * NODE_OVERHEAD_MM2 + n_leaves * LEAF_OVERHEAD_MM2


def tree_area_mm2(features, t_ints, bits, n_leaves: int,
                  dedup: bool = False) -> float:
    """Total bespoke-tree area.

    dedup=False: paper-faithful additive LUT sum (the GA's area estimate).
    dedup=True : synthesis-accurate model — identical (feature, threshold,
      precision) comparators are shared by CSE, as Design Compiler does for
      bespoke circuits. This is this framework's "actual" oracle standing in
      for the paper's DC measurements (the paper's own estimated-vs-actual
      gap in Fig. 5 — HAR/Mammographic/WhiteWine — is exactly a sharing gap).
    """
    import numpy as np
    features = np.asarray(features)
    t_ints = np.asarray(t_ints)
    bits = np.asarray(bits)
    if dedup:
        seen = {}
        for f, t, p in zip(features.tolist(), t_ints.tolist(), bits.tolist()):
            seen[(f, t, p)] = comparator_area_mm2(int(t), int(p))
        comp_area = sum(seen.values())
    else:
        comp_area = sum(comparator_area_mm2(int(t), int(p))
                        for t, p in zip(t_ints.tolist(), bits.tolist()))
    return comp_area + tree_overhead_mm2(len(features), n_leaves)


def power_mw(area_mm2: float) -> float:
    return POWER_PER_MM2_MW * area_mm2


def delay_ms(n_comparators: int) -> float:
    return DELAY_BASE_MS + DELAY_PER_COMP_MS * n_comparators
