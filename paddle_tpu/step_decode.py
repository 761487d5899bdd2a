"""Python twin of the serving daemon's per-slot continuous decode.

Drives a bundle's per-tick decode step export
(io/merged_model.export_decode_step_stablehlo_ex, docs/serving.md
"Step-module bundles") through the SAME slot-scheduler semantics as
native/serving_daemon.cc: a fixed slot array executes the step module
together every tick (live and free slots — the fixed-cost
compiled-step economics); in continuous mode a slot whose request
finished is re-admitted with a NEW request's encoder state at the next
tick (mid-decode), in drain mode admissions only enter an all-idle
batch (classic static batching, the A/B baseline).

Its consumer is the export-parity suite (tests/test_export_parity.py):
tick-by-tick slot decode is bit-identical on ids/ticks to the
whole-``while_loop`` module and to live Python decode, and scheduling
policy never changes results (a mid-decode-admitted request matches its
solo decode exactly).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

_NP_DT = {"f32": np.float32, "i32": np.int32, "i64": np.int64,
          "f64": np.float64, "pred": np.bool_, "u8": np.uint8}


class StepDecodeRequest:
    """One decode request and its per-slot lifecycle record."""

    def __init__(self, feeds: Dict[str, np.ndarray],
                 max_new: Optional[int] = None):
        #: {signature input name: per-slot row array (no slot dim)}
        self.feeds = feeds
        #: per-request tick bound; rides the module carry ("state:cap")
        #: when the export carries it, else scheduler-side truncation
        self.max_new = max_new
        self.slot: Optional[int] = None
        self.admit_tick = -1           # global scheduler tick at admission
        self.mid_batch = False         # admitted while other slots live
        self.tokens: List[int] = []    # streamed best-hypothesis tokens
        self.ids: Optional[np.ndarray] = None      # final [beam, L]
        self.scores: Optional[np.ndarray] = None   # final [beam]
        self.ticks = 0                 # per-slot decode ticks executed

    @property
    def best_ids(self) -> List[int]:
        """Best beam's id sequence cut after the first eos — the
        daemon's /v1/decode response form."""
        row = self.ids[int(np.argmax(self.scores))]
        return list(row[:self._eos_cut(row)])

    def _eos_cut(self, row) -> int:
        eos = getattr(self, "_eos_id", 1)
        hits = np.nonzero(row == eos)[0]
        return int(hits[0]) + 1 if hits.size else len(row)


class StepDecodeDriver:
    """Slot scheduler over a step export's ``init``/``step`` callables.

    ``export`` is the result dict of export_decode_step_stablehlo_ex
    (artifacts deserialized lazily via jax.export). ``drain=True``
    flips to classic static batching. Free slots hold an inert state
    (tick counter at max_length, nothing alive) and keep executing —
    exactly what the daemon's slot array does.
    """

    def __init__(self, export: dict, drain: bool = False):
        from jax import export as jax_export

        self.sig = export["signature"]
        self.S = int(self.sig["slots"])
        self.beam = int(self.sig["beam"])
        self.max_len = int(self.sig["max_length"])
        self.eos_id = int(self.sig["eos_id"])
        self._init = jax_export.deserialize(export["init"]["artifact"])
        self._step = jax_export.deserialize(export["step"]["artifact"])
        self.state_names = [e["name"] for e in self.sig["state"]]
        self.enc_names = [e["name"] for e in self.sig["enc"]]
        self.in_specs = self.sig["inputs"]
        self.drain = bool(drain)
        # inert initial state: nothing alive, counters at max_length
        # (the capped fixpoint), so free slots tick without effect
        self.state = {e["name"]: np.zeros(self._dims(e), _NP_DT[e["dtype"]])
                      for e in self.sig["state"]}
        self.state["state:t"][:] = self.max_len
        if "state:cap" in self.state:   # pre-ISSUE-18 exports lack cap
            self.state["state:cap"][:] = self.max_len
        self.enc = {e["name"]: np.zeros(self._dims(e), _NP_DT[e["dtype"]])
                    for e in self.sig["enc"]}
        self.slot_req: List[Optional[StepDecodeRequest]] = [None] * self.S
        self.queue: List[StepDecodeRequest] = []
        self.finished: List[StepDecodeRequest] = []
        self.tick_count = 0
        self.admissions = {"fresh": 0, "mid_batch": 0}

    def _dims(self, entry) -> tuple:
        return tuple(self.S if d == "b" else int(d)
                     for d in entry["shape"])

    def submit(self, feeds: Dict[str, np.ndarray],
               max_new: Optional[int] = None) -> StepDecodeRequest:
        r = StepDecodeRequest(feeds, max_new=max_new)
        r._eos_id = self.eos_id
        self.queue.append(r)
        return r

    # -- scheduler internals -------------------------------------------

    def _admit(self, slot: int, r: StepDecodeRequest, n_live_entry: int):
        """Run the init module with the request's feeds in row `slot`
        and copy that row of every output into the slot state — the
        daemon's per-admission prefill."""
        flat = []
        for spec in self.in_specs:
            dims = self._dims(spec)
            a = np.zeros(dims, _NP_DT[spec["dtype"]])
            row = np.asarray(r.feeds[spec["name"]], _NP_DT[spec["dtype"]])
            a[slot] = row
            flat.append(a)
        out = [np.array(v) for v in self._init.call(*flat)]
        named = dict(zip(self.sig["init_outputs"], out))
        for n in self.state_names:
            self.state[n][slot] = named[n][slot]
        for n in self.enc_names:
            self.enc[n][slot] = named[n][slot]
        if r.max_new is not None and "state:cap" in self.state:
            # the module's own per-slot bound: this slot goes inert at
            # min(max_new, max_length), neighbors keep their caps
            self.state["state:cap"][slot] = min(int(r.max_new),
                                                self.max_len)
        self.slot_req[slot] = r
        r.slot = slot
        r.admit_tick = self.tick_count
        r.mid_batch = n_live_entry > 0
        self.admissions["mid_batch" if r.mid_batch else "fresh"] += 1

    def _admissions(self):
        n_live = sum(1 for r in self.slot_req if r is not None)
        if self.drain and n_live > 0:
            return
        n_live_entry = n_live
        for s in range(self.S):
            if not self.queue:
                break
            if self.slot_req[s] is not None:
                continue
            self._admit(s, self.queue.pop(0), n_live_entry)

    def tick(self):
        """One scheduler round: admit into free slots, execute the step
        module over the WHOLE slot array, harvest tokens/completions."""
        self._admissions()
        flat = [self.state[n] for n in self.state_names] + \
               [self.enc[n] for n in self.enc_names]
        # np.array (copy): jax hands back read-only views, and admit()
        # writes fresh rows into these buffers between ticks
        out = [np.array(v) for v in self._step.call(*flat)]
        named = dict(zip(self.sig["step_outputs"], out))
        for n in self.state_names:
            self.state[n] = named[n]
        self.tick_count += 1
        for s in range(self.S):
            r = self.slot_req[s]
            if r is None:
                continue
            r.ticks += 1
            r.tokens.append(int(named["emitted"][s]))
            if named["done"][s]:
                r.ids = np.array(self.state["state:ids"][s])
                r.scores = np.array(self.state["state:scores"][s])
                self.finished.append(r)
                self.slot_req[s] = None

    def run(self, max_ticks: Optional[int] = None) -> List[StepDecodeRequest]:
        """Tick until every submitted request finished; returns them in
        completion order."""
        budget = max_ticks if max_ticks is not None else \
            (len(self.queue) + self.S) * (self.max_len + 2)
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and budget > 0:
            self.tick()
            budget -= 1
        if self.queue or any(r is not None for r in self.slot_req):
            raise RuntimeError("step decode did not converge within the "
                               "tick budget (stuck done signal?)")
        return self.finished


def driver_from_bundle_meta(meta: dict, drain: bool = False) \
        -> StepDecodeDriver:
    """Build a driver from a bundle's ``meta.stablehlo_step`` dict (the
    b64 on-disk form read_bundle_meta returns)."""
    import base64

    export = {"signature": meta["signature"],
              "slots": meta["slots"],
              "init": {"artifact": base64.b64decode(
                  meta["init_artifact_b64"])},
              "step": {"artifact": base64.b64decode(
                  meta["step_artifact_b64"])}}
    return StepDecodeDriver(export, drain=drain)
