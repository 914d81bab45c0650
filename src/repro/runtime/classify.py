"""High-throughput classifier serving runtime (DESIGN.md §14).

The end product of the search is one Pareto design — a printed
decision-tree classifier meant to answer feature-vector queries
continuously. `ClassifyServer` loads that design (from a `pareto.json`
point via `search.load_pareto_artifact`, or directly from decoded
`bits`/`t_int` arrays) and serves it at high request rates:

  - **Request micro-batching on power-of-two buckets.** A request of n
    feature vectors pads up to `sweep.round_up_pow2(n)` — the SAME rounding
    rule as the sweep's shape buckets — so the server compiles one step
    program per bucket, not per request size; padding rows are inert
    (row-independent dataflow: a padded row can never change a real row's
    prediction) and are cropped before results return.
  - **Donated ping-pong device buffers.** Each bucket keeps two resident
    `ServeState` slots used alternately; the step donates the incoming
    slot, so XLA reuses its buffers for the outputs and steady-state
    serving never grows the live-array set. Alternation means the host can
    fill one slot's transfer while the device still computes on the other.
    Donation auto-enables on tpu/gpu only (CPU jax has no donation and
    would warn) — the two-slot structure and the zero-realloc invariant
    hold on every backend.
  - **A featurize → batch → classify stage split** (the classifier analogue
    of an LM server's prefill/insert/generate): `featurize` quantizes float
    features to the master 8-bit grid, `batch` pads request codes to bucket
    shape, and the classify step runs the fused inference kernel.
    `tests/test_serve_classifier.py::test_steady_state_serving_allocates_nothing`
    pins the zero-realloc invariant, and `::test_bucket_and_pingpong_invariance`
    pins zero retraces after warm-up.

Every fast path is pinned bit-exact against the gate-level netlist
simulator (`core/netlist.py`) — the oracle triangle (served == tensor
`predict_votes` == netlist sim) is asserted per pareto point in
`tests/test_serve_classifier.py` and by the CLI's `--verify-netlist`.
Integer inputs are sanitized with a mask (`codes & 0xFF`), NOT a clip:
the netlist reads exactly input bits 0..7, so out-of-grid integers wrap
mod 256 in hardware and the server must (and does) agree bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.tree import concatenate_ptrees
from repro.datasets.synthetic import quantize_u8
from repro.kernels import ops as kops
from repro.search.sweep import GRANULE, round_up_pow2

BACKENDS = ("kernel", "reference")


class ServeState(NamedTuple):
    """One resident serving slot: input buffer, predictions, step count."""

    x: jnp.ndarray      # (bucket, F) int32 master codes
    preds: jnp.ndarray  # (bucket,) int32 predicted classes
    count: jnp.ndarray  # () int32 steps this slot has served


@dataclasses.dataclass
class ServeStats:
    """Serving counters (mutated in place by `ClassifyServer`)."""

    n_requests: int = 0
    n_samples: int = 0
    n_steps: int = 0
    steps_per_bucket: dict = dataclasses.field(default_factory=dict)


def _auto_donate() -> bool:
    # buffer donation is a tpu/gpu feature; CPU jax warns and ignores it
    return jax.default_backend() in ("tpu", "gpu")


class ClassifyServer:
    """Serve one fixed approximate tree/forest design under load.

    Parameters
    ----------
    ptrees : list[ParallelTree]
        The trained ensemble layout (e.g. `ParetoArtifact.ptrees()` or
        `search.problem_ptrees(problem)`).
    bits, t_int : (N,) int arrays
        The decoded design — per-comparator precisions and substituted
        integer thresholds (both PRE-truncation) — concatenated across
        trees in `ptrees` order.
    trunc : (N,) int array | None
        Per-comparator truncated-LSB counts (DESIGN.md §16); None = all
        exact. Folded into effective operands exactly as the search's
        fitness path and the netlist lowering do.
    vote_adder : "exact" (popcount vote adder) or "approx" (saturating
        OR-tree, DESIGN.md §16). Inert for single trees.
    n_classes : int
    n_features : int | None
        Feature-vector width; defaults to the widest feature index any
        comparator reads + 1 (requests may be wider — unused columns are
        ignored, exactly as in the circuit).
    backend : "kernel" (fused Pallas inference, the serving fast path) or
        "reference" (the pure-jnp `predict_votes` dataflow). Both are
        pinned bit-exact to the netlist oracle.
    max_batch : largest bucket; requests beyond it split into chunks.
    granule : smallest bucket (shared with the sweep's `GRANULE`).
    interpret : Pallas interpreter override (None = auto: interpret off-TPU).
    donate : donate the ping-pong slot to the step (None = auto: tpu/gpu).
    """

    def __init__(self, ptrees, bits, t_int, n_classes: int,
                 n_features: int | None = None, *, trunc=None,
                 vote_adder: str = "exact", backend: str = "kernel",
                 max_batch: int = 1024, granule: int = GRANULE,
                 interpret: bool | None = None, donate: bool | None = None):
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown serving backend {backend!r}; options: {BACKENDS}")
        if max_batch < granule:
            raise ValueError(f"max_batch={max_batch} < granule={granule}")
        if vote_adder not in quant.VOTE_ADDER_MODES:
            raise ValueError(
                f"unknown vote_adder {vote_adder!r}; "
                f"options: {quant.VOTE_ADDER_MODES}")
        arrays = concatenate_ptrees(ptrees)
        self.feature = np.asarray(arrays["feature"], np.int32)
        n = self.feature.shape[0]
        bits = np.asarray(bits, np.int32)
        t_int = np.asarray(t_int, np.int32)
        trunc = (np.zeros(n, np.int32) if trunc is None
                 else np.asarray(trunc, np.int32))
        if bits.shape != (n,) or t_int.shape != (n,) or trunc.shape != (n,):
            raise ValueError(
                f"design arrays bits{bits.shape}/t_int{t_int.shape}/"
                f"trunc{trunc.shape} do not match the ensemble's "
                f"{n} comparators")
        if n and (trunc.min() < 0 or trunc.max() > quant.MAX_TRUNC):
            raise ValueError(
                f"trunc values must lie in [0, {quant.MAX_TRUNC}], got "
                f"range [{trunc.min()}, {trunc.max()}]")
        self.bits = bits
        self.t_int = t_int
        self.trunc = trunc
        self.vote_adder = vote_adder
        self.n_classes = int(n_classes)
        self.n_features = int(n_features) if n_features is not None else (
            int(self.feature.max()) + 1 if n else 1)
        if n and self.n_features <= int(self.feature.max()):
            raise ValueError(
                f"n_features={self.n_features} but a comparator reads "
                f"feature {int(self.feature.max())}")
        self.backend = backend
        self.max_batch = int(max_batch)
        self.granule = int(granule)
        self.interpret = interpret
        self.donate = _auto_donate() if donate is None else bool(donate)
        self.stats = ServeStats()

        # design + operands are built ONCE; every bucket's step closes over
        # the same device arrays (the chromosome-invariant prep of §12,
        # specialised to a single fixed design)
        self._design = kops.prepare_design(bits, t_int, trunc=trunc,
                                           vote_adder=vote_adder)
        self._operands = kops.prepare_operands(
            arrays["feature"], arrays["path"], arrays["path_len"],
            arrays["n_neg"], arrays["leaf_class"], self.n_classes,
            self.n_features)
        # reference-backend operands (the predict_votes dataflow) —
        # EFFECTIVE values: truncation folded into precision/threshold,
        # vote cap 1.0 for the approximate adder (DESIGN.md §16)
        self._ref = dict(
            feature=jnp.asarray(self.feature),
            bits=jnp.asarray(bits - trunc),
            t_int=jnp.asarray(t_int >> trunc),
            vote_cap=jnp.float32(
                1.0 if vote_adder == "approx" else np.inf),
            path_t=jnp.asarray(np.asarray(arrays["path"]).T
                               .astype(np.float32)),
            target=jnp.asarray((np.asarray(arrays["path_len"])
                                - np.asarray(arrays["n_neg"]))
                               .astype(np.float32)),
            cls1h=jax.nn.one_hot(jnp.asarray(arrays["leaf_class"]),
                                 self.n_classes),
        )

        self.family = "tree"
        self._steps: dict[int, object] = {}      # bucket -> jitted step
        self._slots: dict[int, list] = {}        # bucket -> [state, state]
        self._slot_idx: dict[int, int] = {}

    # -- construction ------------------------------------------------------

    @classmethod
    def for_mlp(cls, w1, w2, shift: int, n_classes: int,
                n_features: int | None = None, *, backend: str = "kernel",
                max_batch: int = 1024, granule: int = GRANULE,
                interpret: bool | None = None,
                donate: bool | None = None) -> "ClassifyServer":
        """Serve a printed-MLP design (effective integer weights).

        Same bucketed ping-pong machinery as the tree server — only `_infer`
        differs: `kernel` routes the first layer through `kernels.qmatmul`
        (int8 weights), `reference` is the pure-jnp matmul; both are
        integer-exact in f32 and pinned to `core.netlist.build_mlp_circuit`.
        """
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown serving backend {backend!r}; options: {BACKENDS}")
        if max_batch < granule:
            raise ValueError(f"max_batch={max_batch} < granule={granule}")
        w1 = np.asarray(w1, np.int32)
        w2 = np.asarray(w2, np.int32)
        if w1.ndim != 2 or w2.ndim != 2 or w2.shape[0] != w1.shape[1]:
            raise ValueError(
                f"weight shapes w1{w1.shape}/w2{w2.shape} do not chain "
                f"(expected (F, H) @ (H, C))")
        if w2.shape[1] != n_classes:
            raise ValueError(
                f"w2 has {w2.shape[1]} output columns for "
                f"n_classes={n_classes}")
        self = cls.__new__(cls)
        self.family = "mlp"
        self.w1 = w1
        self.w2 = w2
        self.shift = int(shift)
        self.n_classes = int(n_classes)
        self.n_features = (int(n_features) if n_features is not None
                           else int(w1.shape[0]))
        if self.n_features != w1.shape[0]:
            raise ValueError(
                f"n_features={self.n_features} but w1 reads {w1.shape[0]} "
                f"features")
        self.backend = backend
        self.max_batch = int(max_batch)
        self.granule = int(granule)
        self.interpret = interpret
        self.donate = _auto_donate() if donate is None else bool(donate)
        self.stats = ServeStats()
        self._mlp = dict(
            w1_i8=jnp.asarray(w1, jnp.int8),
            w1_f=jnp.asarray(w1, jnp.float32),
            w2_f=jnp.asarray(w2, jnp.float32),
            ones=jnp.ones((w1.shape[1],), jnp.float32),
            shift_scale=jnp.float32(2.0 ** -self.shift),
        )
        self._steps = {}
        self._slots = {}
        self._slot_idx = {}
        return self

    @classmethod
    def from_artifact(cls, artifact, point: int | str = "best",
                      max_loss: float = 0.01, **opts) -> "ClassifyServer":
        """Serve a `pareto.json` point.

        ``artifact`` is a loaded artifact of ANY family (tree
        `search.ParetoArtifact` or MLP `families.printed_mlp.
        MlpParetoArtifact`) or a path to pareto.json; ``point`` selects the
        pareto index, or "best" for the smallest-area point within
        ``max_loss``. The design re-materializes from the artifact alone
        (DESIGN.md §14/§15).
        """
        from repro.search import artifact as _artifact

        if isinstance(artifact, str):
            artifact = _artifact.load_pareto_artifact(artifact)
        if point == "best":
            idx = artifact.best_under_loss(max_loss)
            if idx is None:
                raise ValueError(
                    f"no pareto point within max_loss={max_loss}; "
                    f"losses: {[p['acc_loss'] for p in artifact.points]}")
        else:
            idx = int(point)
            if not 0 <= idx < len(artifact.points):
                raise ValueError(
                    f"pareto point {idx} out of range "
                    f"(artifact has {len(artifact.points)} points)")
        if getattr(artifact, "family", "tree") == "mlp":
            w1, w2 = artifact.point_design(idx)
            server = cls.for_mlp(w1, w2, artifact.shift, artifact.n_classes,
                                 artifact.n_features, **opts)
        else:
            bits, t_int, trunc, vote_adder = artifact.point_design(idx)
            server = cls(artifact.ptrees(), bits, t_int, artifact.n_classes,
                         trunc=trunc, vote_adder=vote_adder, **opts)
        server.artifact = artifact
        server.point_index = idx
        return server

    # -- the three serving stages -----------------------------------------

    def featurize(self, x) -> np.ndarray:
        """Float features in [0, 1] (n, F) -> master 8-bit codes (n, F)."""
        return quantize_u8(np.asarray(x))

    def sanitize(self, codes) -> np.ndarray:
        """Integer codes -> the 8 input bits the circuit actually reads.

        A MASK, not a clip: `core.netlist.simulate` reads bits 0..7 of each
        input, so any integer wraps mod 256 in hardware — serving must
        reproduce that bit-for-bit for out-of-grid values too.
        """
        return (np.asarray(codes).astype(np.int64) & 0xFF).astype(np.int32)

    def bucket_for(self, n: int) -> int:
        """Power-of-two batch bucket serving a request of n rows."""
        return min(self.max_batch, round_up_pow2(n, self.granule))

    def batch(self, codes) -> list[tuple[np.ndarray, int]]:
        """Pad request codes up to bucket shape(s).

        Returns [(padded (bucket, F) int32, n_real)], one entry per
        `max_batch` chunk (a single entry for requests that fit one
        bucket). Padding rows are zero — inert by row independence.
        """
        codes = np.asarray(codes, np.int32)
        if codes.ndim != 2:
            raise ValueError(f"expected (n, F) codes, got shape {codes.shape}")
        if codes.shape[1] < self.n_features:
            raise ValueError(
                f"request has {codes.shape[1]} features; the design reads "
                f"{self.n_features}")
        out = []
        for lo in range(0, codes.shape[0], self.max_batch) or [0]:
            chunk = codes[lo:lo + self.max_batch]
            bucket = self.bucket_for(chunk.shape[0])
            padded = np.zeros((bucket, codes.shape[1]), np.int32)
            padded[:chunk.shape[0]] = chunk
            out.append((padded, chunk.shape[0]))
        return out

    def classify_codes(self, codes) -> np.ndarray:
        """(n, F) integer master codes -> (n,) predicted classes."""
        codes = self.sanitize(codes)
        self.stats.n_requests += 1
        self.stats.n_samples += int(codes.shape[0])
        if codes.shape[0] == 0:
            return np.zeros((0,), np.int32)
        preds = [np.asarray(self.step(padded))[:n]
                 for padded, n in self.batch(codes)]
        return np.concatenate(preds).astype(np.int32)

    def classify(self, x) -> np.ndarray:
        """Serve one request: (n, F) features -> (n,) predicted classes.

        Float inputs are featurized to the master grid; integer inputs are
        taken as codes (masked to the circuit's 8 input bits). Non-finite
        float features (NaN/±inf) are rejected with a `ValueError` before
        the float->int quantization cast — `np.floor(nan).astype(int)` is
        undefined behavior, and a printed sensor frontend feeding NaN is a
        fault the caller must see, not a silently-served garbage class.
        """
        x = np.asarray(x)
        if np.issubdtype(x.dtype, np.integer):
            return self.classify_codes(x)
        bad = ~np.isfinite(x)
        if bad.any():
            rows = np.unique(np.nonzero(bad)[0])[:8]
            raise ValueError(
                f"classify: non-finite feature values (NaN/inf) in "
                f"{int(bad.sum())} entries (rows {rows.tolist()}...); "
                f"features must be finite floats in [0, 1]")
        return self.classify_codes(self.featurize(x))

    # -- bucketed ping-pong step ------------------------------------------

    def step(self, padded: np.ndarray):
        """Run one bucket-shaped batch through the resident step.

        `padded` is (bucket, F) int32 from `batch`. Returns the device
        predictions array (bucket,) — callers crop to the real row count.
        """
        bucket = int(padded.shape[0])
        step_fn = self._steps.get(bucket)
        if step_fn is None:
            step_fn = self._steps[bucket] = self._build_step(bucket)
            self._slots[bucket] = [None, None]
            self._slot_idx[bucket] = 0
        idx = self._slot_idx[bucket]
        state = self._slots[bucket][idx]
        if state is None:  # warmup: allocate this slot's resident buffers
            state = ServeState(
                x=jnp.zeros(padded.shape, jnp.int32),
                preds=jnp.zeros((bucket,), jnp.int32),
                count=jnp.int32(0))
        state = step_fn(state, jnp.asarray(padded))
        self._slots[bucket][idx] = state
        self._slot_idx[bucket] = idx ^ 1  # ping-pong
        self.stats.n_steps += 1
        self.stats.steps_per_bucket[bucket] = (
            self.stats.steps_per_bucket.get(bucket, 0) + 1)
        return state.preds

    def _infer(self, x8):
        """(bucket, F) codes -> (bucket,) predictions, selected backend."""
        if self.family == "mlp":
            m = self._mlp
            xf = x8[:, :self.n_features].astype(jnp.float32)
            if self.backend == "kernel":
                h = kops.qmatmul(xf, m["w1_i8"], m["ones"],
                                 interpret=self.interpret)
            else:
                h = xf @ m["w1_f"]
            hq = jnp.floor(jnp.maximum(h, 0.0) * m["shift_scale"])
            return jnp.argmax(hq @ m["w2_f"], axis=1).astype(jnp.int32)
        if self.backend == "kernel":
            bucket = x8.shape[0]
            return kops.classify(
                x8, self._operands, self._design,
                block_b=min(256, bucket),
                interpret=self.interpret).astype(jnp.int32)
        r = self._ref
        x_p = quant.inputs_at_precision(x8[:, r["feature"]], r["bits"])
        t_sub = r["t_int"][None, :]
        d = (x_p > t_sub).astype(jnp.float32)
        score = d @ r["path_t"]
        sat = (score == r["target"][None, :]).astype(jnp.float32)
        votes = sat @ r["cls1h"]
        # saturating (approximate) vote adder: +inf cap = exact f32 no-op
        votes = jnp.minimum(votes, r["vote_cap"])
        return jnp.argmax(votes, axis=1).astype(jnp.int32)

    def _build_step(self, bucket: int):
        def step(state: ServeState, x_new) -> ServeState:
            return ServeState(x=x_new, preds=self._infer(x_new),
                              count=state.count + 1)

        donate = (0,) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    # -- accounting --------------------------------------------------------

    def compiled_buckets(self) -> list[int]:
        return sorted(self._steps)

    def compile_count(self) -> int:
        """Total compiled step specializations across buckets — steady-state
        serving must not grow this (pinned at 0 new compiles after warm-up by
        `tests/test_serve_classifier.py::test_bucket_and_pingpong_invariance`)."""
        return sum(fn._cache_size() for fn in self._steps.values())
