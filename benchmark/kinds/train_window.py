"""Traffic kind ``train_window``: boosting rounds for ``--seconds``.

Set-up makes the rows (the configuration's own table where it names a
``table_seed``, its columns in the seed's order: every seed the same
trees; else a table drawn from the seed), bins them through ``lgb.Dataset``,
builds ONE ``Booster`` and drives it through its first ``checked_rounds``
by the window's own call (which also compiles the round program); the
window then goes on with that same object.  Every call is
``Booster.update_many(rounds_per_call)`` ended by ``block_until_ready``,
so the window closes on a dispatch boundary and its real length divides
the work.

``correct`` comes from ``benchmark/reference/gbdt_check.py`` once the
window has closed and the program's device state is freed: the first
rounds' trees (as ``Booster.dump_model()`` hands them to a user) and
training scores against exact statistics of the raw rows, the splits
they chose against the reference's own search of the same nodes, their
split order against leaf-wise growth, the leaf count of every tree of
the run, and the final scores of a sample of rows against a walk over
every tree of the run.  Nothing may compile inside the window.

Another kind is a subclass that brings its inputs (``make_inputs``), the
reference's rounds (``reference_rounds``), the score the reference starts
from (``init_score``), its own compared numbers and read counters
(``own_checks``) and faults of its own (``PARAM_FAULTS``); ``check`` is
this module's alone.
"""

from __future__ import annotations

import time

import numpy as np

from .. import datagen
from ..reference import gbdt_check


class Cell:
    # Faults that break the timed path from outside: a call that leaves the
    # booster as it was, half of the rows' statistics left out, one leaf of
    # round 2 altered where it is produced.
    TIMED_PATH_FAULTS = ("state_unchanged", "half_batch", "altered_answer")
    # Faults that are a path of the program's own, switched on by a
    # parameter: ``(params, config) -> params to merge``.  A grower that
    # stops at half the leaves, a split scan over half of the features, wave
    # growth without the replay of strict best-first order.
    PARAM_FAULTS = {
        "fewer_leaves": lambda p, cfg: {
            "num_leaves": (int(p["num_leaves"]) + 1) // 2},
        "restricted_features": lambda p, cfg: {"feature_fraction": 0.5},
        "greedy_tail": lambda p, cfg: {"wave_tail": "greedy"},
    }

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 fault: str = None):
        """``fault``: one of ``TIMED_PATH_FAULTS`` or ``PARAM_FAULTS`` for
        this run alone (the CPU tests and ``benchmark/readings.py`` plant
        them to see ``correct`` come out false); nothing on the command line
        or in the environment sets it."""
        if fault is not None and fault not in self.TIMED_PATH_FAULTS \
                and fault not in self.PARAM_FAULTS:
            raise ValueError(f"benchmark: no fault {fault!r} in this kind")
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.devices, self.fault = devices, fault
        self.rows = int(config["rows"])
        self.features = int(config["features"])
        self.rounds_per_call = int(traffic.get("rounds_per_call", 1))
        self.checked_rounds = int(traffic.get("checked_rounds", 3))
        self.sample_rows = int(traffic.get("sample_rows", 100_000))
        # the nodes the reference searches and the leaves it replays in each
        # checked tree: none where no limit reads them
        limits = config["limits"]
        searched = "split_gain_short" in limits or "order_excess" in limits
        self.split_nodes = int(traffic.get("split_nodes", 0)) if searched else 0
        self.order_leaves = \
            int(traffic.get("order_leaves", 0)) if searched else 0
        self.counters = {}
        from ..device import CompileMeter

        self.meter = CompileMeter()

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        self.make_inputs()
        self.build()

    def make_inputs(self) -> None:
        """Rows and labels, binned through ``lgb.Dataset``: the
        configuration's table in the seed's column order, or, where the
        configuration names no ``table_seed``, a table of the seed's own."""
        import jax

        import lightgbm_tpu as lgb

        t0 = time.perf_counter()
        table_seed = self.config.get("table_seed")
        self.X, self.y = datagen.higgs_like(
            self.rows, self.features,
            self.seed if table_seed is None else int(table_seed))
        t_made = time.perf_counter()
        if table_seed is not None:
            datagen.reorder_columns(self.X, self.seed)
        t1 = time.perf_counter()
        self.dataset = lgb.Dataset(self.X, label=self.y, free_raw_data=True)
        self.dataset.construct()
        jax.block_until_ready(self.dataset.X_binned)
        self.counters.update(datagen_s=t1 - t0, reorder_s=t1 - t_made,
                             binning_s=time.perf_counter() - t1)

    def share_inputs(self, other: "Cell") -> None:
        """Another run on the same rows (``benchmark/readings.py``)."""
        self.X, self.y, self.dataset = other.X, other.y, other.dataset
        self.counters.update(datagen_s=0.0, reorder_s=0.0, binning_s=0.0)

    def build(self) -> None:
        """ONE booster, driven through its first rounds by the window's
        own call; the window goes on with the same object."""
        import jax

        import lightgbm_tpu as lgb

        ds = self.dataset
        t2 = time.perf_counter()
        params = dict(self.config["params"])
        if self.fault in self.PARAM_FAULTS:
            params.update(self.PARAM_FAULTS[self.fault](params, self.config))
        self.booster = lgb.Booster(params, ds)
        if self.fault == "half_batch":
            # half of the batch left out, the statistics taken over the rest
            half = np.ones(int(ds.row_mask.shape[0]), np.float32)
            half[1::2] = 0.0
            self.booster._bag = self.booster._bag * jax.numpy.asarray(half)
        self.program_init = float(self.booster.init_score_)
        self.scores_after = []
        for _ in range(self.checked_rounds):
            self._call()
            self.scores_after.append(
                np.asarray(self.booster._pred_train)[:self.rows])
        t3 = time.perf_counter()
        self.counters.update(
            first_rounds_s=t3 - t2,
            rows=self.rows, features=self.features,
            rows_padded=int(ds.row_mask.shape[0]),
            code_bytes=int(ds.X_binned.dtype.itemsize))

    def _call(self) -> None:
        """The window's call and feed, used by set-up's checked rounds too."""
        import jax

        before = self.booster.current_iteration()
        if self.fault != "state_unchanged":
            with jax.profiler.TraceAnnotation("bench.update_many"):
                self.booster.update_many(self.rounds_per_call)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(self.booster._pred_train)
        if self.fault == "altered_answer" and before == 1:
            # one answer altered where it is produced: a leaf of round 2
            tree = self.booster.trees[1]
            i = int(np.flatnonzero(np.asarray(tree.is_leaf))[0])
            self.booster.trees[1] = tree._replace(
                leaf_value=tree.leaf_value.at[i].multiply(1.5))

    # -- the measured window -----------------------------------------------
    def window(self, seconds: float) -> dict:
        import jax

        call_s = []
        with self.meter.measure(), \
                jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                self._call()
                elapsed = time.perf_counter() - t0
                call_s.append(elapsed)
                if elapsed >= seconds:
                    break
        calls = len(call_s)
        rounds = calls * self.rounds_per_call
        # window_call_s: the window's clock after every call, so that
        # ``benchmark/spread.py`` can read what a shorter window of the
        # same run would have measured; its last entry is window_s
        self.counters.update(window_rounds=rounds, window_calls=calls,
                             window_compiles=self.meter.programs,
                             window_call_s=call_s,
                             rounds_per_call=self.rounds_per_call)
        return {
            "window_s": elapsed,
            "attempted": calls,
            "failed": 0,
            "metrics": {
                "train_rows_rounds_per_s": self.rows * rounds / elapsed,
            },
        }

    # -- after the window --------------------------------------------------
    def release(self) -> None:
        """Take the program's answer to the host and free its device state."""
        b = self.booster
        total = b.current_iteration()
        dump = b.dump_model()
        self.trees = [gbdt_check.flatten_tree(t["tree_structure"])
                      for t in dump["tree_info"]]
        rng = np.random.default_rng(self.seed)
        self.sample = np.sort(rng.choice(
            self.rows, size=min(self.sample_rows, self.rows), replace=False))
        self.final_sample_scores = np.asarray(b._pred_train)[self.sample]
        self.counters["total_rounds"] = total
        self.counters.update(self.round_memory())
        del self.booster, self.dataset, b

    def round_memory(self) -> dict:
        """The compiled round's own account of its device memory (XLA's
        ``memory_analysis``), to set beside the runtime's counters that
        ``memory_peak_bytes`` is read from.  Read after the peak, so the
        second copy of the program that this loads is not in it."""
        try:
            fn, args = self.booster._fused_segment(self.rounds_per_call)
            ma = fn.lower(*args).compile().memory_analysis()
            return {"round_temp_bytes": int(ma.temp_size_in_bytes),
                    "round_argument_bytes": int(ma.argument_size_in_bytes),
                    "round_output_bytes": int(ma.output_size_in_bytes)}
        except Exception as e:       # the reading is a counter, not a check
            return {"round_memory_error": 1, "round_memory_why": repr(e)}

    def leaf_counts(self) -> list:
        """The leaves of every tree of the run."""
        return [int((t["feature"] < 0).sum()) for t in self.trees]

    def init_score(self) -> float:
        """The score the reference starts every row from."""
        return gbdt_check.init_score(self.y)

    def reference_rounds(self, k: int) -> list:
        """The reference's numbers of each of the first ``k`` rounds, one
        dict a round (``gbdt_check.check_rounds``)."""
        return gbdt_check.check_rounds(
            self.X, self.y, self.trees[:k], self.scores_after,
            self.program_init, self.config["reference"], seed=self.seed,
            split_nodes=self.split_nodes,
            order_leaves=self.order_leaves)["rounds"]

    def own_checks(self, rounds) -> list:
        """The kind's own compared numbers ``[(name, value, limit)]``, and
        what it reads without comparing, into ``counters``; ``rounds`` is
        ``reference_rounds``' answer, ``None`` where the run holds fewer
        trees than it checks."""
        if rounds is not None:
            self.counters["reference_loss"] = [rd["loss"] for rd in rounds]
        return []

    def check(self) -> list:
        """``[(name, value, limit)]``: a value over its limit is a fault."""
        limits = self.config["limits"]
        hyper = self.config["reference"]
        k = self.checked_rounds
        rounds = self.reference_rounds(k) if len(self.trees) >= k else None
        out = []
        expected = k + self.counters.get("window_rounds", 0)
        out.append(("trees_missing",
                    float(abs(expected - len(self.trees))), 0.0))
        out.append(("compiles_in_window",
                    float(self.counters.get("window_compiles", 0)), 0.0))
        if self.trees and "leaves_off" in limits:
            out.append(("leaves_off", float(max(
                abs(n - int(hyper["num_leaves"])) for n in self.leaf_counts())),
                limits["leaves_off"]))
        out += self.own_checks(rounds)
        if rounds is not None:
            for name in ("leaf_value_worst", "leaf_count_off", "score_abs",
                         "split_gain_short", "order_excess"):
                if name in limits:
                    worst = max(rd[name] for rd in rounds)
                    out.append((name, float(worst), limits[name]))
            if self.split_nodes or self.order_leaves:
                self.counters["split_checks"] = [
                    [rd["nodes_checked"], rd["leaves_checked"]]
                    for rd in rounds]
            # read, not compared: no control or fault reads far enough
            # above the sound runs (PERF.md, section 2)
            self.counters["leaf_value_rms"] = max(
                rd["leaf_value_rms"] for rd in rounds)
        if self.trees and "final_score_abs" in limits:
            gap = gbdt_check.check_sample(
                self.X[self.sample], self.trees, self.init_score(),
                float(hyper["learning_rate"]), self.final_sample_scores)
            out.append(("final_score_abs", gap, limits["final_score_abs"]))
        return out
