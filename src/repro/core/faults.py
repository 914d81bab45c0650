"""Gate-level stuck-at fault injection on the netlist IR (DESIGN.md §17).

Printed circuits are fabricated at yields where individual gates *will*
fail, and the question that decides whether a Pareto design is shippable is
not its defect-free accuracy but what accuracy survives when gates stick.
This module turns any `core.netlist.Circuit` into a fault-injection target:

  - `enumerate_fault_sites(circuit)` lists every injectable site — the
    output of each logic gate plus each primary-input bit (INPUT gates).
    Constants are not sites: a CONST gate *is* a stuck wire already.
  - `FaultSimulator` evaluates **fault-lanes x test-vectors in one batched
    program**: the single-lane evaluator mirrors `netlist.simulate`'s
    levelized schedule gate-for-gate (same `levelize`, same per-level
    gather/op expressions), then applies the lane's stuck-at overrides as a
    per-level mask (`where(stuck_mask[level_gates], stuck_val, computed)`),
    and `jax.vmap` lifts it over a whole chunk of fault lanes at once.
    A lane with an empty mask is therefore *bit-identical* to
    `netlist.simulate` — the zero-fault invariant
    `tests/test_faults.py::test_zero_fault_bit_identical_to_simulate` pins at
    exactly 0 mismatches.
  - `simulate_faulty_serial` is the deliberately naive oracle: a pure
    Python/numpy loop over gates in topological order with the fault
    applied on the way. The vmapped campaign is pinned array-for-array
    against it in `tests/test_faults.py`.

Fault lanes are expressed as dense (G,) stuck masks + values, so one
simulator serves both campaign shapes: single stuck-at faults are one-hot
masks (`site_masks`), Monte-Carlo defect draws are multi-hot masks sampled
by `search.robustness` under fixed PRNG keys. Chunks are padded to a fixed
lane count so a campaign compiles at most one program per (chunk, batch)
shape regardless of how many sites a circuit has.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.netlist import (
    AND,
    CONST1,
    INPUT,
    NOT,
    OR,
    Circuit,
    levelize,
)

# fault-lane chunk sizing: lanes per dispatch are chosen so a chunk's
# boolean value tensor (chunk, B, G) stays under this budget
DEFAULT_CHUNK_BUDGET_BYTES = 64 << 20
MAX_CHUNK = 256

# the `call=` id of the profiler spans of one `FaultSimulator.run_masks` call
_CALLS = itertools.count()


@dataclasses.dataclass(frozen=True)
class FaultSite:
    """One injectable stuck-at location.

    `gate` indexes the circuit's gate arrays; `kind` is "input" for a
    primary-input bit (op == INPUT, where `feature`/`bit` name the master
    code bit) and "gate" for a logic-gate output; `label` is the stable
    human-readable name used in fault reports."""

    gate: int
    kind: str       # "input" | "gate"
    op: str         # OP_NAMES entry ("input", "not", "and", "or", "xor")
    label: str
    feature: int = -1   # input sites only
    bit: int = -1       # input sites only


def enumerate_fault_sites(circuit: Circuit) -> list[FaultSite]:
    """Every injectable site: logic-gate outputs + primary-input bits.

    Sites are ordered by gate id (deterministic); constants are excluded —
    CONST0/CONST1 are stuck wires by definition, and the hash-consed
    builder guarantees they occupy gates 0 and 1.
    """
    from repro.core.netlist import OP_NAMES

    sites = []
    for g in range(circuit.n_gates):
        op = int(circuit.op[g])
        if op <= CONST1:
            continue
        if op == INPUT:
            f, b = int(circuit.a[g]), int(circuit.b[g])
            sites.append(FaultSite(g, "input", "input",
                                   f"input[f{f}.b{b}]", feature=f, bit=b))
        else:
            sites.append(FaultSite(g, "gate", OP_NAMES[op],
                                   f"{OP_NAMES[op]}@{g}"))
    return sites


def site_masks(n_gates: int, gates, values) -> tuple[np.ndarray, np.ndarray]:
    """One-hot (S, G) stuck mask/value pairs for single-fault lanes."""
    gates = np.asarray(gates, np.int64)
    values = np.asarray(values)
    if gates.shape != values.shape:
        raise ValueError(
            f"gates {gates.shape} and values {values.shape} differ")
    s = gates.shape[0]
    mask = np.zeros((s, n_gates), bool)
    val = np.zeros((s, n_gates), bool)
    mask[np.arange(s), gates] = True
    val[np.arange(s), gates] = values.astype(bool)
    return mask, val


def single_fault_lanes(circuit: Circuit, sites=None):
    """(gates (2S,), values (2S,)) covering stuck-at-0 AND stuck-at-1 of
    every site — fault lane 2k is site k stuck-at-0, lane 2k+1 stuck-at-1."""
    if sites is None:
        sites = enumerate_fault_sites(circuit)
    gates = np.repeat(np.asarray([s.gate for s in sites], np.int64), 2)
    values = np.tile(np.asarray([0, 1], np.int64), len(sites))
    return gates, values


def auto_chunk(circuit: Circuit, n_samples: int,
               budget_bytes: int = DEFAULT_CHUNK_BUDGET_BYTES) -> int:
    """Fault lanes per dispatch keeping the (chunk, B, G) bool tensor under
    `budget_bytes` (clamped to [1, MAX_CHUNK])."""
    per_lane = max(1, int(n_samples) * circuit.n_gates)
    return int(np.clip(budget_bytes // per_lane, 1, MAX_CHUNK))


class FaultSimulator:
    """Vmapped stuck-at simulator over one circuit's levelized schedule.

    The per-lane evaluator repeats `netlist.simulate`'s exact computation
    (same levels, same masked gathers, same boolean expressions) with one
    addition: after each level's outputs are computed — the base level
    included — the lane's stuck-at override is applied as a mask, so a
    stuck gate presents its stuck value to every consumer while its own
    operand evaluation is unchanged (the standard stuck-at model).
    """

    def __init__(self, circuit: Circuit):
        self.circuit = circuit
        level = levelize(circuit)
        logic = np.asarray(circuit.op) >= NOT
        self._base = np.flatnonzero(level == 0)
        self._levels = [np.flatnonzero(level == lvl)
                        for lvl in range(1, int(level.max()) + 1
                                         if logic.any() else 1)]
        self._vmapped = jax.jit(
            jax.vmap(self._sim_one, in_axes=(None, 0, 0)))

    # -- the single-lane evaluator (mirror of netlist.simulate) ------------
    def _sim_one(self, x8, stuck_mask, stuck_val):
        """(B, F) codes + (G,) stuck mask/value -> (B,) predicted class."""
        circuit = self.circuit
        op, a, b = circuit.op, circuit.a, circuit.b
        g = circuit.n_gates
        n_b = x8.shape[0]
        vals = jnp.zeros((n_b, g), jnp.bool_)

        base = self._base
        feat = np.maximum(a[base], 0)
        bit = np.maximum(b[base], 0)
        in_vals = ((x8[:, feat] >> bit[None, :]) & 1).astype(jnp.bool_)
        base_ops = op[base][None, :]
        base_vals = jnp.where(base_ops == INPUT, in_vals, base_ops == CONST1)
        base_vals = jnp.where(stuck_mask[base][None, :],
                              stuck_val[base][None, :], base_vals)
        vals = vals.at[:, base].set(base_vals)

        for idx in self._levels:
            if idx.size == 0:
                continue
            av = vals[:, a[idx]]
            bv = vals[:, np.maximum(b[idx], 0)]
            ops = op[idx][None, :]
            out = jnp.where(
                ops == NOT, ~av,
                jnp.where(ops == AND, av & bv,
                          jnp.where(ops == OR, av | bv, av ^ bv)))
            out = jnp.where(stuck_mask[idx][None, :],
                            stuck_val[idx][None, :], out)
            vals = vals.at[:, idx].set(out)

        cls = jnp.zeros((n_b,), jnp.int32)
        for i, w in enumerate(circuit.out_bits):
            cls = cls | (vals[:, w].astype(jnp.int32) << i)
        return cls

    # -- batched campaigns -------------------------------------------------
    def run_masks(self, x8, stuck_mask, stuck_val,
                  chunk: int | None = None) -> np.ndarray:
        """(S, G) stuck masks/values -> (S, B) predictions.

        Lanes run in chunks of `chunk` (auto-sized to the memory budget by
        default); the final chunk pads with zero-fault lanes and crops, so
        at most one program compiles per (chunk, batch) shape.

        The masks go to the device once, every chunk is dispatched without
        waiting for the one before, and the predictions come back in one
        copy at the end. So a call holds all its lanes on the device until
        it returns: S x G x 2 bytes of masks and S x B x 4 bytes of
        predictions, padding included (the exhaustive HAR campaign,
        5,408 lanes of a 2,706-gate circuit over 3,090 samples: 29 MB and
        67 MB), beside one chunk's (chunk, B, G) bool table.
        """
        from repro.runtime import spans

        x8 = jnp.asarray(x8, jnp.int32)
        stuck_mask = np.asarray(stuck_mask, bool)
        stuck_val = np.asarray(stuck_val, bool)
        if stuck_mask.ndim != 2 or stuck_mask.shape[1] != self.circuit.n_gates:
            raise ValueError(
                f"stuck masks must be (S, {self.circuit.n_gates}), got "
                f"{stuck_mask.shape}")
        if stuck_val.shape != stuck_mask.shape:
            raise ValueError(
                f"stuck values {stuck_val.shape} do not match masks "
                f"{stuck_mask.shape}")
        s = stuck_mask.shape[0]
        if chunk is None:
            chunk = auto_chunk(self.circuit, int(x8.shape[0]))
        chunk = max(1, min(int(chunk), max(s, 1)))
        n_chunks = -(-s // chunk)
        with spans.span("faults.run", call=next(_CALLS)):
            spans.count("faults.lanes", s)
            spans.count("faults.dispatches", n_chunks)
            if not n_chunks:
                return np.zeros((0, int(x8.shape[0])), np.int32)
            pad = ((0, n_chunks * chunk - s), (0, 0))
            masks, vals = jax.device_put(
                (np.pad(stuck_mask, pad).reshape(n_chunks, chunk, -1),
                 np.pad(stuck_val, pad).reshape(n_chunks, chunk, -1)))
            # iterating a device array splits it in one program of its own
            preds = [self._vmapped(x8, m, v) for m, v in zip(masks, vals)]
            with spans.span("faults.fetch"):  # the call's one wait
                preds = jax.device_get(preds)
        return np.concatenate(preds)[:s]

    def run_sites(self, x8, gates, values,
                  chunk: int | None = None) -> np.ndarray:
        """Single-fault lanes: (S,) site gates + stuck values -> (S, B)."""
        mask, val = site_masks(self.circuit.n_gates, gates, values)
        return self.run_masks(x8, mask, val, chunk=chunk)

    def run_zero_fault(self, x8) -> np.ndarray:
        """(B,) predictions of the defect-free lane — must be bit-identical
        to `netlist.simulate` (the mask is empty, so the levelized programs
        compute the same booleans in the same order)."""
        g = self.circuit.n_gates
        empty = np.zeros((1, g), bool)
        return self.run_masks(x8, empty, empty, chunk=1)[0]


def simulate_faulty_serial(circuit: Circuit, x8, faults=()) -> np.ndarray:
    """Serial per-gate oracle: (B,) predictions under `faults`.

    `faults` is an iterable of (gate, stuck_value) pairs. Evaluates gates
    one at a time in topological order with plain numpy — the reference the
    vmapped `FaultSimulator` is pinned against, sharing no jnp code with it.
    """
    x8 = np.asarray(x8, np.int64)
    n_b = x8.shape[0]
    op, a, b = circuit.op, circuit.a, circuit.b
    stuck = {int(g): bool(v) for g, v in faults}
    vals = np.zeros((circuit.n_gates, n_b), bool)
    for g in range(circuit.n_gates):
        o = int(op[g])
        if o == CONST1:
            v = np.ones(n_b, bool)
        elif o == INPUT:
            v = ((x8[:, int(a[g])] >> int(b[g])) & 1).astype(bool)
        elif o == NOT:
            v = ~vals[int(a[g])]
        elif o == AND:
            v = vals[int(a[g])] & vals[int(b[g])]
        elif o == OR:
            v = vals[int(a[g])] | vals[int(b[g])]
        elif o == NOT + 3:  # XOR (opcode 6)
            v = vals[int(a[g])] ^ vals[int(b[g])]
        else:               # CONST0
            v = np.zeros(n_b, bool)
        if g in stuck:
            v = np.full(n_b, stuck[g])
        vals[g] = v
    cls = np.zeros(n_b, np.int32)
    for i, w in enumerate(circuit.out_bits):
        cls |= vals[w].astype(np.int32) << i
    return cls
