"""Traffic kind ``train_window_cat``: ``train_window`` for a table whose
categorical columns are given to the program as ``categorical_feature``.

``train_window``'s ``Cell`` (one booster driven through its checked rounds
by the window's own call, the window, the compiled round's memory account
and ``check``) with what is its own:

* **inputs**: ``datagen_cat.allstate_like``: the configuration's ``rows``
  x 32 columns of an Allstate-shaped table from its ``table_seed``, the
  columns in the seed's order (``datagen.reorder_columns``; the kind
  follows its categorical columns there), given to ``lgb.Dataset`` with
  those columns as ``categorical_feature``;
* **the categorical facts**: before any training the kind asks the
  booster for its round program (``Booster._fused_segment``) and traces
  it (``lower``, no dispatch; the first dispatch reuses the trace), and
  copies the facts the program noted, ``dataset.categorical_features``,
  ``.codes_path``, ``train.cat_features`` and ``train.cat_route``, into
  its counters (``categorical_features``, ``codes_path``, ``cat_features``,
  ``cat_route``).  A program that notes no ``train.cat_route`` does not
  grow category sets as this cell measures them: the run ends there,
  before a round is trained, with no result line;
* **the answer** (``release``): ``train_window``'s, the trees flattened by
  ``benchmark/reference/cat_check.py`` (a categorical split's LEFT set of
  raw values as a set of rows), and ``cat_split_pct``, 100 x the window's
  category-set splits over all its splits, read from the window's trees;
* **the reference** (``reference_rounds``): ``cat_check``, which knows only
  raw values.  ``own_checks`` compares ``cat_dump_faults`` (splits of a
  categorical column dumped as anything but a set that sends NaN right)
  and ``final_walk_abs`` (the final scores of ``sample_rows`` seeded rows
  against a walk over every tree of the run that routes category sets:
  ``train_window``'s ``final_score_abs`` walks thresholds only, so the
  configuration names no limit of that name), and reads, without
  comparing, the reference's loss and the checked trees' set splits;
* **``cat_scan_ms``**: after the window, the program's categorical split
  scan ALONE (``Booster._cat_scan_call``) on the root's real histogram,
  median of 5 (neither set-up's time nor the window's).

Faults: the inherited ones and ``numeric_codes``: the same table given to
``lgb.Dataset`` with no ``categorical_feature``, so that its category
numbers are read as ordered thresholds.
"""

from __future__ import annotations

import time

import numpy as np

from .. import datagen, datagen_cat
from ..reference import cat_check
from . import train_window as tw

CAT_FACTS = ("dataset.categorical_features", "dataset.codes_path",
             "train.cat_features", "train.cat_route")


class Cell(tw.Cell):
    TIMED_PATH_FAULTS = tw.Cell.TIMED_PATH_FAULTS + ("numeric_codes",)

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 fault: str = None):
        super().__init__(config, traffic, seed, devices, fault)
        self._facts_read = False
        self.table = None

    # -- set-up ------------------------------------------------------------
    def make_inputs(self) -> None:
        t0 = time.perf_counter()
        if self.features != datagen_cat.FEATURES:
            raise ValueError(
                f"the Allstate layout has {datagen_cat.FEATURES} columns")
        table_seed = self.config.get("table_seed")
        self.X, self.y = datagen_cat.allstate_like(
            self.rows, self.seed if table_seed is None else int(table_seed))
        t_made = time.perf_counter()
        order = np.arange(self.features)
        if table_seed is not None:
            datagen.reorder_columns(self.X, self.seed)
            # the order reorder_columns drew
            order = np.random.default_rng(int(self.seed)).permutation(
                self.features)
        self.categorical = datagen_cat.categorical_after(order)
        t1 = time.perf_counter()
        self.bin()
        self.counters.update(datagen_s=t1 - t0, reorder_s=t1 - t_made,
                             binning_s=time.perf_counter() - t1)

    def bin(self) -> None:
        """``lgb.Dataset`` of the rows, the categorical columns given as
        ``categorical_feature`` (none under ``numeric_codes``)."""
        import jax

        import lightgbm_tpu as lgb

        given = [] if self.fault == "numeric_codes" else self.categorical
        self.dataset = lgb.Dataset(
            self.X, label=self.y, free_raw_data=True,
            categorical_feature=given,
            params={k: self.config["params"][k] for k in (
                "max_bin", "min_data_in_bin")
                if k in self.config["params"]})
        self.dataset.construct()
        jax.block_until_ready(self.dataset.X_binned)

    def share_inputs(self, other: "Cell") -> None:
        super().share_inputs(other)
        if other.table is None:         # the reference's codes, made once
            other.table = cat_check.Table(other.X, other.categorical)
        self.categorical, self.table = other.categorical, other.table
        if self.fault == "numeric_codes":
            self.bin()

    def _call(self) -> None:
        if not self._facts_read:
            self.read_cat_facts()
        super()._call()

    def read_cat_facts(self) -> None:
        """Before the first round: the facts the program notes for the
        table and its round program, copied into the counters; none, no
        run (but under ``numeric_codes``, which gives no categorical
        column)."""
        from ..readers.program_span import snapshot

        self._facts_read = True
        fn, args = self.booster._fused_segment(self.rounds_per_call)
        # the grower notes where it routes category sets as it is traced
        fn.lower(*args)
        facts = snapshot().get("facts", {})
        if self.fault == "numeric_codes":
            return
        missing = [f for f in CAT_FACTS if f not in facts]
        if missing:
            raise SystemExit(
                "benchmark: the program notes no " + ", ".join(missing)
                + " for this table: it does not grow category sets as this "
                "cell measures them, so the cell's counters cannot be "
                "filled: nothing was trained, no number is printed")
        for f in CAT_FACTS:
            self.counters[f.split(".", 1)[1]] = facts[f]

    # -- after the window --------------------------------------------------
    def release(self) -> None:
        """``train_window``'s release, the trees flattened with their
        category sets; the probe first, while the booster is there."""
        self.probe_cat_scan()
        b = self.booster
        total = b.current_iteration()
        dump = b.dump_model()
        structures = [t["tree_structure"] for t in dump["tree_info"]]
        self.counters["cat_dump_faults"] = sum(
            cat_check.dump_faults(s, set(self.categorical))
            for s in structures)
        if self.table is None:
            self.table = cat_check.Table(self.X, self.categorical)
        self.trees = [cat_check.flatten_tree(s, self.table)
                      for s in structures]
        window = self.trees[self.checked_rounds:]
        splits = sum(int((t["feature"] >= 0).sum()) for t in window)
        if splits:
            self.counters["cat_split_pct"] = 100.0 * sum(
                int(t["is_cat"].sum()) for t in window) / splits
        rng = np.random.default_rng(self.seed)
        self.sample = np.sort(rng.choice(
            self.rows, size=min(self.sample_rows, self.rows), replace=False))
        self.final_sample_scores = np.asarray(b._pred_train)[self.sample]
        self.counters["total_rounds"] = total
        self.counters.update(self.round_memory())
        del self.booster, self.dataset, b

    def probe_cat_scan(self) -> None:
        """The program's categorical split scan alone
        (``Booster._cat_scan_call``: the jitted scan and its operands, the
        root's histogram at the booster's scores after the window's last
        round): median of 5, in milliseconds.  After the window and the
        memory's peak, so its seconds (``cat_scan_s``, its build and the
        histogram included) are in neither ``setup_s`` nor the window; a
        probe that fails fails the run."""
        import jax

        t_start = time.perf_counter()
        call = self.booster._cat_scan_call()
        if call is None:                # no categorical column
            return
        fn, args = call
        jax.block_until_ready(fn(*args))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            took.append(time.perf_counter() - t0)
        self.counters["cat_scan_ms"] = 1000.0 * float(np.median(took))
        self.counters["cat_scan_s"] = time.perf_counter() - t_start

    # -- the reference -----------------------------------------------------
    def reference_rounds(self, k: int) -> list:
        return cat_check.check_rounds(
            self.table, self.y, self.trees[:k], self.scores_after,
            self.program_init, self.config["reference"], seed=self.seed,
            split_nodes=self.split_nodes,
            order_leaves=self.order_leaves)["rounds"]

    def own_checks(self, rounds) -> list:
        limits = self.config["limits"]
        hyper = self.config["reference"]
        out = [("cat_dump_faults",
                float(self.counters.get("cat_dump_faults", 0)),
                limits["cat_dump_faults"])]
        if self.trees and "final_walk_abs" in limits:
            gap = cat_check.check_sample(
                self.table, self.sample, self.trees, self.init_score(),
                float(hyper["learning_rate"]), self.final_sample_scores)
            out.append(("final_walk_abs", gap, limits["final_walk_abs"]))
        if rounds is not None:
            # read, not compared
            for key in ("loss", "cat_splits", "split_worst"):
                self.counters["reference_" + key] = [
                    rd[key] for rd in rounds if key in rd]
        return out
