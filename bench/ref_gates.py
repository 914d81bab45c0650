"""Plain reference for single stuck-at fault simulation of a gate netlist.

Imports nothing of the program. The circuit is the campaign's input, as
plain arrays: per gate an opcode (0 constant 0, 1 constant 1, 2 input bit
``b`` of master code feature ``a``, 3 NOT a, 4 a AND b, 5 a OR b, 6 a XOR
b), operand gate ids that precede the gate, and the wires that carry the
class index, least significant bit first. A stuck-at fault forces one
gate's output to 0 or 1 for every gate that reads it.

Gates are evaluated one at a time in id order, over the test samples
packed eight to a byte, for a group of fault lanes at once.
"""
from __future__ import annotations

import numpy as np

CONST0, CONST1, INPUT, NOT, AND, OR, XOR = range(7)


def simulate(op, a, b, out_bits, x8, stuck_gate=None, stuck_value=None,
             group: int = 32) -> np.ndarray:
    """(S, B) predicted class under each lane's single stuck-at fault.

    ``stuck_gate``/``stuck_value`` are (S,) arrays (gate id, 0 or 1); with
    neither, one fault-free lane is simulated.
    """
    op = np.asarray(op, np.int64)
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    x8 = np.asarray(x8, np.int64)
    if stuck_gate is None:
        stuck_gate = np.array([-1])
        stuck_value = np.array([0])
    stuck_gate = np.asarray(stuck_gate, np.int64)
    stuck_value = np.asarray(stuck_value, np.int64)
    n_b = x8.shape[0]
    out = []
    for lo in range(0, stuck_gate.shape[0], group):
        out.append(_simulate_group(op, a, b, out_bits, x8,
                                   stuck_gate[lo:lo + group],
                                   stuck_value[lo:lo + group], n_b))
    return np.concatenate(out, axis=0)


def _simulate_group(op, a, b, out_bits, x8, gates, values, n_b):
    s = gates.shape[0]
    n_bytes = (n_b + 7) // 8
    vals = np.zeros((op.shape[0], s, n_bytes), np.uint8)
    ones = np.full((s, n_bytes), 0xFF, np.uint8)
    stuck = {}
    for lane, (g, v) in enumerate(zip(gates.tolist(), values.tolist())):
        stuck.setdefault(g, []).append((lane, v))
    for g in range(op.shape[0]):
        o = op[g]
        if o == CONST0:
            v = np.zeros((s, n_bytes), np.uint8)
        elif o == CONST1:
            v = ones.copy()
        elif o == INPUT:
            bit = ((x8[:, a[g]] >> b[g]) & 1).astype(np.uint8)
            v = np.broadcast_to(np.packbits(bit), (s, n_bytes)).copy()
        elif o == NOT:
            v = ~vals[a[g]]
        elif o == AND:
            v = vals[a[g]] & vals[b[g]]
        elif o == OR:
            v = vals[a[g]] | vals[b[g]]
        elif o == XOR:
            v = vals[a[g]] ^ vals[b[g]]
        else:
            raise ValueError(f"gate {g}: unknown opcode {o}")
        for lane, value in stuck.get(g, ()):
            v[lane] = 0xFF if value else 0
        vals[g] = v
    cls = np.zeros((s, n_b), np.int64)
    for i, w in enumerate(out_bits):
        bits = np.unpackbits(vals[w], axis=-1, count=n_b).astype(np.int64)
        cls |= bits << i
    return cls
