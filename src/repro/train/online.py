"""Online in-memory TA training under live traffic (arXiv:2408.09456).

The companion paper to IMPACT performs Tsetlin-automata *updates* in the
same Y-Flash array inference reads from; IMBUE (arXiv:2305.12914) carries
the feedback on the same Boolean-to-current datapath.  ``OnlineTrainer``
reproduces that loop on an already-deployed ``IMPACTSystem``:

1. **Feedback sweep (analog read).**  Clause outputs come off the clause
   crossbar (the CSA datapath, training semantics: empty clauses fire),
   class votes off the digital weight copy — the hybrid analog-clause /
   digital-vote split of the companion paper's feedback controller.
2. **TA transitions (compiled kernel).**  The Type I/II delta matmuls run
   through the session's registered ``ta_feedback`` primitive (Pallas
   kernel or einsum oracle — bit-identical by the parity contract).
3. **In-array write-back (pulse trains).**  Only TAs whose *action*
   flipped touch the array: ``pulse_until`` drives exactly those cells
   across the Boolean HCS/LCS boundary with ``program_pulse``/
   ``erase_pulse`` trains, under the same D2D/C2C variability model the
   read path uses (per-device tau/asymptote spread sampled once per
   grid, per-pulse log-normal C2C noise).  Changed weight cells re-tune
   the class tile within the paper's fine-tune tolerance band.
4. **Billing.**  Write energy comes from the ACTUAL pulse counts via
   ``encode_energy`` into the ``write_energy_j`` lane of the standard
   ``EnergyReport`` — so an interleaved train+serve run aggregates
   training joules and serving joules through one meter stack, and a
   zero-flip update bills exactly 0.0 J (no pulses, no energy).

The write-back mutates the ``IMPACTSystem`` arrays in place and refreshes
every compiled ``InferenceSession`` cached on it: operand shapes never
change, so serving sessions pick up the new conductances WITHOUT a
retrace — updates and requests interleave through the same engine seam.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cotm import CoTMConfig, CoTMParams, class_scores, include_mask
from ..core.train import _int_matmul, apply_deltas
from ..impact import tiles as tiles_mod
from ..impact import yflash
from ..impact.energy import EnergyReport, encode_energy
from ..impact.tiles import weight_targets
from ..impact.yflash import (DeviceVariation, G_HCS_BOOL, G_LCS,
                             I_CSA_THRESHOLD, read_current)
from ..kernels import backends as backends_mod
from ..kernels import packing as packing_mod
from ..kernels.ref import pad_to

Array = jax.Array


class OnlineTrainer:
    """Interleaved in-array CoTM training on a deployed ``IMPACTSystem``.

    ``session`` must be a plain (non-co-resident, unpacked) compiled
    session of the system being trained; its backend lowers the
    ``ta_feedback`` primitive and its spec's interpret policy applies.
    ``params`` are the digital TA/weight copies the deployed system was
    encoded from (the feedback controller state).  ``variability=False``
    gives the ideal-device twin: no D2D spread, no C2C write noise.
    """

    def __init__(self, session, params: CoTMParams, cfg: CoTMConfig, *,
                 key: Array, pulse_width: float = 1e-3,
                 class_pulse_width: float = 50e-6,
                 weight_tol_segments: float = 5.0, max_pulses: int = 64,
                 variability: bool = True, trace=None):
        if session.spec.coresident is not None:
            raise ValueError(
                "OnlineTrainer needs a single-tenant session — training "
                "writes re-program the shared fabric under a co-resident "
                "plan's feet (train the member system, then rebalance)")
        if session.spec.packing == "2bit":
            raise ValueError(
                "OnlineTrainer needs an unpacked session — the write path "
                "targets the f32 conductance grid (packed serving "
                "sessions cached on the same system are re-packed after "
                "every update)")
        self.session = session
        self.system = session.system
        self.params = params
        self.cfg = cfg
        self.pulse_width = float(pulse_width)
        self.class_pulse_width = float(class_pulse_width)
        self.max_pulses = int(max_pulses)
        self.variability = bool(variability)
        self.trace = trace

        sys_ = self.system
        R, C, tr, tc = sys_.clause_i.shape
        S, sr, m = sys_.class_i.shape
        # The weight->conductance map is FROZEN at encode time: the same
        # unipolar shift and segment scale the class tile was programmed
        # with.  Weights running past the encoded range saturate at the
        # band edges (a physical conductance range, not an error).
        self._shift = int(sys_.encode_stats["weight_shift"])
        self._w_max = max(int(sys_.encode_stats["weights"]["w_max"]), 1)
        seg = (yflash.G_RANGE_HI - yflash.G_RANGE_LO) / self._w_max
        self._w_tol = float(weight_tol_segments) * seg
        self._w_uni_pad = self._unipolar_padded(params.weights)

        # D2D variability is a property of the physical cells: sampled
        # ONCE per grid here and reused by every write sweep (the read
        # path's spread is already baked into the encoded conductances).
        k_cl, k_cls, self._key = jax.random.split(key, 3)
        if self.variability:
            self._clause_var = DeviceVariation.sample(k_cl, (R * tr, C * tc))
            self._class_var = DeviceVariation.sample(k_cls, (S * sr, m))
        else:
            self._clause_var = DeviceVariation.none((R * tr, C * tc))
            self._class_var = DeviceVariation.none((S * sr, m))

        self.records: list[dict[str, Any]] = []
        self.reports: list[EnergyReport] = []
        self._step = 0

    @property
    def write_energy_j(self) -> float:
        """f64 write meter: the per-update bills in ``records`` summed
        with the same ``sum`` that ``aggregate_reports`` applies to
        ``reports``, so meter, per-record total and aggregated report lane
        are equal by construction.  (A separate ``+=`` running total
        rounds differently: ``sum`` compensates float additions on
        Python >= 3.12.)"""
        return float(sum(r["write_energy_j"] for r in self.records))

    # -- helpers ------------------------------------------------------------
    def _unipolar_padded(self, weights: Array) -> Array:
        S, sr, m = self.system.class_i.shape
        w_uni = jnp.clip(weights + self._shift, 0, self._w_max)
        return pad_to(w_uni.T.astype(jnp.int32), S * sr, 0)       # (S*sr, m)

    def _refresh_sessions(self) -> None:
        """Propagate the mutated grid into every compiled session.  The
        operand arrays are re-read per call (same shapes — no retrace),
        but the nonempty mask and any compile-time packed operand are
        cached on the session and must be refreshed by hand."""
        sys_ = self.system
        sessions = list(sys_.__dict__.get("_sessions", {}).values())
        if self.session not in sessions:
            sessions.append(self.session)
        for sess in sessions:
            sess._nonempty = sys_._nonempty_eff()
            if sess._packed is not None:
                sess._packed = packing_mod.pack_clause_operand(sys_.clause_i)

    def evaluate(self, literals: Array, labels: Array) -> float:
        """Held-out accuracy through the ANALOG serving path (the same
        compiled ``predict`` executable live traffic rides)."""
        preds = np.asarray(self.session.predict(literals).predictions)
        return float((preds == np.asarray(labels)).mean())

    # -- one update sweep ---------------------------------------------------
    def update(self, literals: Array, labels: Array,
               key: Array | None = None) -> dict[str, Any]:
        """One batched Type I/II update: analog feedback sweep, compiled
        ``ta_feedback`` deltas, in-array pulse-train write-back.  Returns
        the per-update billing/convergence record (also appended to
        ``records``; a matching ``EnergyReport`` with this update's
        ``write_energy_j`` is appended to ``reports``)."""
        t0 = self.trace.clock() if self.trace is not None else 0.0
        if key is None:
            self._key, key = jax.random.split(self._key)
        cfg = self.cfg
        sys_ = self.system
        B, K = literals.shape
        n, m, T = cfg.n_clauses, cfg.n_classes, cfg.threshold

        # 1. Analog feedback sweep: clause bits off the crossbar with
        # TRAINING semantics (the all-ones mask lets empty clauses fire,
        # exactly ``clause_outputs(..., training=True)``); votes off the
        # digital weight copy.
        lit = jnp.asarray(literals, jnp.int8)
        inc = include_mask(self.params.ta_state, cfg.n_states)
        fired, i_col = backends_mod.get_backend(
            self.session.spec.backend).impact_clause_bits(
                lit, sys_.clause_i, jnp.ones_like(sys_.nonempty),
                thresh=I_CSA_THRESHOLD,
                interpret=self.session.spec.interpret)
        fired = fired[:, :n]
        scores = class_scores(fired, self.params.weights)

        # 2. Feedback masks (identical construction to
        # ``core.train.batch_deltas``) + the compiled delta primitive.
        k_neg, k_sel, k_hi, k_lo, k_wc, k_ww = jax.random.split(key, 6)
        labels = jnp.asarray(labels, jnp.int32)
        neg = (labels + jax.random.randint(k_neg, (B,), 1, m)) % m
        tgt = jnp.concatenate([labels, neg])                      # (2B,)
        pol = jnp.concatenate([jnp.ones(B, jnp.int32),
                               -jnp.ones(B, jnp.int32)])
        rows = jnp.arange(B)
        v = jnp.clip(jnp.concatenate([scores[rows, labels],
                                      scores[rows, neg]]), -T, T)
        p = (T - pol * v).astype(jnp.float32) / (2 * T)
        sel = jax.random.bernoulli(k_sel, p[:, None], (2 * B, n))
        sign = jnp.where(self.params.weights[tgt] >= 0, 1, -1)
        match = sign == pol[:, None]
        fired2 = jnp.concatenate([fired, fired])                  # (2B, n)
        lit2 = jnp.concatenate([lit, lit], axis=0)                # (2B, K)
        s = cfg.specificity
        hi = (jnp.ones((K, n), jnp.int32) if cfg.boost_true_positive
              else jax.random.bernoulli(
                  k_hi, (s - 1.0) / s, (K, n)).astype(jnp.int32))
        lo = jax.random.bernoulli(k_lo, 1.0 / s,
                                  (K, n)).astype(jnp.int32)
        ta_delta = self.session.ta_feedback(lit2, fired2, sel, match,
                                            hi, lo, inc)
        onehot = jax.nn.one_hot(tgt, m, dtype=jnp.int8).T
        w_upd = (pol[:, None] * (sel & fired2)).astype(jnp.int8)
        w_delta = _int_matmul(onehot, w_upd)
        new_params = apply_deltas(self.params, ta_delta, w_delta, cfg)

        # 3. Write-back: only ACTION flips touch the clause array.
        R, C, tr, tc = sys_.clause_i.shape
        S, sr, _ = sys_.class_i.shape
        inc_new = include_mask(new_params.ta_state, cfg.n_states)
        flip = pad_to(pad_to(inc_new != inc, R * tr, 0), C * tc, 1)
        inc_pad = pad_to(pad_to(inc_new, R * tr, 0), C * tc, 1)
        g_cl = sys_.clause_g.transpose(0, 2, 1, 3).reshape(R * tr, C * tc)
        # Untouched cells get the trivial band [0, inf): zero pulses by
        # construction, so an update with no flips bills exactly 0.0 J.
        tlo = jnp.where(flip & inc_pad, G_HCS_BOOL, 0.0)
        thi = jnp.where(flip, jnp.where(inc_pad, jnp.inf, G_LCS), jnp.inf)
        g_cl, np_cl, ne_cl = yflash.pulse_until(
            g_cl, target_lo=tlo, target_hi=thi,
            width_prog=self.pulse_width, width_erase=self.pulse_width,
            var=self._clause_var, key=k_wc, max_pulses=self.max_pulses,
            c2c=self.variability)
        unconv = tiles_mod.n_unconverged(g_cl, tlo, thi)

        # Changed weight cells re-tune within the fine-tune band.
        w_uni_new = self._unipolar_padded(new_params.weights)
        changed = w_uni_new != self._w_uni_pad
        target = weight_targets(w_uni_new, self._w_max)
        wlo = jnp.where(changed, target - self._w_tol, 0.0)
        whi = jnp.where(changed, target + self._w_tol, jnp.inf)
        g_cls = sys_.class_g.reshape(S * sr, m)
        g_cls, np_w, ne_w = yflash.pulse_until(
            g_cls, target_lo=wlo, target_hi=whi,
            width_prog=self.class_pulse_width,
            width_erase=self.class_pulse_width,
            var=self._class_var, key=k_ww, max_pulses=self.max_pulses,
            c2c=self.variability)
        unconv += tiles_mod.n_unconverged(g_cls, wlo, whi)

        # 4. Bill the ACTUAL pulses (f64 host-side, like every meter).
        e_p_cl, e_e_cl = encode_energy(np_cl, ne_cl, self.pulse_width,
                                       self.pulse_width)
        e_p_w, e_e_w = encode_energy(np_w, ne_w, self.class_pulse_width,
                                     self.class_pulse_width)
        e_write = float(e_p_cl + e_e_cl + e_p_w + e_e_w)
        # The feedback sweep's clause read bills like any serving read.
        e_read = float(yflash.V_READ * np.float64(np.asarray(i_col).sum())
                       * yflash.T_READ)

        # 5. Mutate the system in place + refresh every cached session.
        sys_.clause_g = g_cl.reshape(R, tr, C, tc).transpose(0, 2, 1, 3)
        sys_.clause_i = read_current(sys_.clause_g)
        sys_.class_g = g_cls.reshape(S, sr, m)
        sys_.class_i = read_current(sys_.class_g)
        sys_.nonempty = pad_to(inc_new.any(axis=0), C * tc, 0)
        self._refresh_sessions()
        self.params = new_params
        self._w_uni_pad = w_uni_new

        record = dict(
            step=self._step,
            write_energy_j=e_write,
            read_energy_j=e_read,
            prog_pulses=int(np_cl.sum()) + int(np_w.sum()),
            erase_pulses=int(ne_cl.sum()) + int(ne_w.sum()),
            n_unconverged=int(unconv),
            n_flips=int(jnp.sum(inc_new != inc)),
            n_weight_cells=int(changed.sum()),
        )
        self.records.append(record)
        self.reports.append(EnergyReport(
            read_energy_j=e_read, clause_energy_j=e_read,
            class_energy_j=0.0,
            program_energy_j=sys_.encode_stats["program_energy_j"],
            erase_energy_j=sys_.encode_stats["erase_energy_j"],
            latency_s=sys_._grid_latency(), ops_crosspoint=B * K * n,
            datapoints=B, write_energy_j=e_write))
        self._step += 1
        if self.trace is not None:
            self.trace.span("train_update", t0, self.trace.clock(),
                            args=dict(step=record["step"],
                                      write_energy_j=e_write,
                                      n_flips=record["n_flips"],
                                      n_unconverged=record["n_unconverged"]))
        return record
