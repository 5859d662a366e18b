"""Traffic kind ``train_window_sparse``: ``train_window`` for a wide table
that is mostly missing values, whose sparse columns the program bundles
(Exclusive Feature Bundling).

``train_window``'s ``Cell`` (one booster driven through its checked rounds
by the window's own call, the window, the release, the compiled round's
memory account and ``check``) with what is its own:

* **inputs**: ``datagen_sparse.bosch_like``: the configuration's ``rows``
  x ``features`` columns, NaN where a part did not pass the column's
  station, and 0.58 % failures, from the configuration's ``table_seed``
  (``--seed`` orders the columns, ``datagen.reorder_columns``, and draws
  what the reference samples);
* **the bundles' own counts**: before any training the kind asks the
  booster for its round program (``Booster._fused_segment``, no dispatch)
  and copies the facts the program noted for the table and for it,
  ``dataset.bundle_columns``, ``.bundled_features``,
  ``.bundle_conflict_rows``, ``.codes_path`` and ``train.features``,
  ``.features_raw``, into its counters (``bundle_columns``, ...,
  ``train_features``, ``train_features_raw``; ``efb_column_ratio`` is
  ``train.features`` / ``train.features_raw``).  A program that notes none
  of them grows on this table some other way, and its counters could not
  be filled: the run ends there, before a round is trained, with no result
  line;
* **the reference** (``reference_rounds``): ``benchmark/reference/
  sparse_check.py``, which knows nothing of bundles: NaN routed right,
  candidate thresholds over each column's values and "a value or NaN",
  short trees checked for a leaf that could still split
  (``unsplit_leaves``).  ``own_checks`` compares ``bundle_conflict_rows``
  (rows on which two members of one bundle are off their defaults: the
  table's groups of stations are exclusive, so none), ``dump_missing``
  (splits whose dump does not send NaN right) and ``unsplit_leaves``, and
  reads, without comparing, the reference's loss and leaves a tree;
* **``efb_member_scan_ms``**: after the window, the program's member view
  and split scan ALONE (``Booster._member_scan_call``) on the root's real
  histogram, median of 5 (neither set-up's time nor the window's).

Faults: the inherited ones, with ``fewer_leaves`` a grower stopped at 4
leaves (at ``min_sum_hessian_in_leaf`` 100 and 0.58 % failures a tree
stops far short of half the budget by itself, so halving ``num_leaves``
changes no tree) and without ``greedy_tail`` (the leaf budget never binds,
so the tail's policy changes no tree either).
"""

from __future__ import annotations

import time

import numpy as np

from .. import datagen, datagen_sparse
from ..reference import sparse_check
from . import train_window as tw

SPARSE_FACTS = ("dataset.bundle_columns", "dataset.bundled_features",
                "dataset.bundle_conflict_rows", "dataset.codes_path",
                "train.features", "train.features_raw")


class Cell(tw.Cell):
    PARAM_FAULTS = dict(
        {k: v for k, v in tw.Cell.PARAM_FAULTS.items()
         if k != "greedy_tail"},
        fewer_leaves=lambda p, cfg: {"num_leaves": 4})

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 fault: str = None):
        super().__init__(config, traffic, seed, devices, fault)
        self._facts_read = False

    # -- set-up ------------------------------------------------------------
    def make_inputs(self) -> None:
        import jax

        import lightgbm_tpu as lgb

        t0 = time.perf_counter()
        table_seed = self.config.get("table_seed")
        self.X, self.y = datagen_sparse.bosch_like(
            self.rows, self.features,
            self.seed if table_seed is None else int(table_seed))
        t_made = time.perf_counter()
        if table_seed is not None:
            datagen.reorder_columns(self.X, self.seed)
        t1 = time.perf_counter()
        self.dataset = lgb.Dataset(
            self.X, label=self.y, free_raw_data=True,
            params={k: self.config["params"][k] for k in (
                "max_bin", "enable_bundle", "max_conflict_rate")
                if k in self.config["params"]})
        self.dataset.construct()
        jax.block_until_ready(self.dataset.X_binned)
        self.counters.update(datagen_s=t1 - t0, reorder_s=t1 - t_made,
                             binning_s=time.perf_counter() - t1)

    def _call(self) -> None:
        if not self._facts_read:
            self.read_bundle_facts()
        super()._call()

    def read_bundle_facts(self) -> None:
        """Before the first round: the facts the program notes for the
        table and its round program, copied into the counters; none, no
        run."""
        from ..readers.program_span import snapshot

        self._facts_read = True
        self.booster._fused_segment(self.rounds_per_call)
        facts = snapshot().get("facts", {})
        missing = [f for f in SPARSE_FACTS if f not in facts]
        if missing:
            raise SystemExit(
                "benchmark: the program notes no " + ", ".join(missing)
                + " for this table: its bundles cannot be read, so the "
                "cell's counters cannot be filled: nothing was trained, no "
                "number is printed")
        for f in SPARSE_FACTS:
            self.counters[f.replace("dataset.", "").replace(".", "_")] = \
                facts[f]
        self.counters["efb_column_ratio"] = (
            facts["train.features"] / facts["train.features_raw"])

    # -- after the window --------------------------------------------------
    def release(self) -> None:
        self.probe_member_scan()
        # the dump's own word on missing values, split by split
        self.counters["dump_missing_splits"] = sum(
            sparse_check.dump_missing(t["tree_structure"])
            for t in self.booster.dump_model()["tree_info"])
        super().release()

    def probe_member_scan(self) -> None:
        """The program's member view + split scan alone
        (``Booster._member_scan_call``: the jitted pair and its operands,
        the root's histogram at the booster's scores after the window's
        last round): median of 5, in milliseconds.  After the window and
        the memory's peak, so its seconds (``efb_member_scan_s``, its
        build and the histogram included) are in neither ``setup_s`` nor
        the window; a probe that fails fails the run."""
        import jax

        t_start = time.perf_counter()
        call = self.booster._member_scan_call()
        if call is None:                # a table with no bundle
            return
        fn, args = call
        jax.block_until_ready(fn(*args))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            took.append(time.perf_counter() - t0)
        self.counters["efb_member_scan_ms"] = 1000.0 * float(np.median(took))
        self.counters["efb_member_scan_s"] = time.perf_counter() - t_start

    # -- the reference -----------------------------------------------------
    def reference_rounds(self, k: int) -> list:
        return sparse_check.check_rounds(
            self.X, self.y, self.trees[:k], self.scores_after,
            self.program_init, self.config["reference"], seed=self.seed,
            split_nodes=self.split_nodes,
            order_leaves=self.order_leaves)["rounds"]

    def own_checks(self, rounds) -> list:
        limits = self.config["limits"]
        out = [("bundle_conflict_rows",
                float(self.counters.get("bundle_conflict_rows", 0)),
                limits["bundle_conflict_rows"]),
               ("dump_missing",
                float(self.counters.get("dump_missing_splits", 0)),
                limits["dump_missing"])]
        if self.trees:
            leaves = self.leaf_counts()
            self.counters["leaves_least"] = min(leaves)
            self.counters["leaves_most"] = max(leaves)
        if rounds is not None:
            # read, not compared
            for key in ("loss", "leaves", "hessian_sum"):
                self.counters["reference_" + key] = [rd[key] for rd in rounds]
            if "unsplit_leaves" in rounds[0]:
                out.append(("unsplit_leaves", float(max(
                    rd["unsplit_leaves"] for rd in rounds)),
                    limits["unsplit_leaves"]))
        return out

