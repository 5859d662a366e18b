"""Traffic kind ``train_window_rank``: ``train_window`` for a learning-to-
rank table, boosting rounds of ``objective=lambdarank`` for ``--seconds``.

``train_window``'s ``Cell`` (one booster driven through its checked rounds
by the window's own call, the window, the release, the compiled round's
memory account and ``check``) with what is its own:

* **inputs**: ``datagen_rank.mslr_like``: the configuration's ``queries``
  query groups of ``query_docs`` documents (rows group-contiguous), its
  ``rows`` x ``features`` columns and labels 0-4, from the configuration's
  ``table_seed`` (``--seed`` orders the columns, ``datagen.reorder_columns``,
  and draws what the reference samples; the groups and labels are the
  table's); ``lgb.Dataset`` is handed ``group=``;
* **the layout's own counts**: before any training the kind asks the
  booster for its round program (``Booster._fused_segment``, no dispatch)
  and copies the facts the program noted for it, ``train.rank_queries``,
  ``.rank_doc_slots``, ``.rank_pair_slots``, ``.rank_pairs_visited``,
  ``.rank_truncation``, ``.rank_blocks``, into its counters (the metrics
  ``rank_pad_ratio`` and ``rank_pair_slot_ratio`` read them).  A program
  that notes none of them packs its queries some other way, and its
  counters could not be filled: the run ends there, before a round is
  trained, with no result line;
* **the reference** (``reference_rounds``, ``init_score``):
  ``benchmark/reference/rank_check.py``: LightGBM's lambdarank gradients in
  float64, query by query, from the scores the program stored, then what
  ``gbdt_check`` does with them; initial score 0.  ``own_checks`` compares
  ``init_abs`` (the program's initial score against 0) and reads, without
  comparing, ``reference_ndcg10`` (NDCG@10 of the training scores after
  each checked round), ``reference_leaves``, ``reference_hessian_sum``,
  ``own_rank_flips``, ``own_grad_rows`` and ``own_grad_gap`` (what ranking
  by the program's stored scores hides: ``rank_check.own_walk_gap``).
  ``rank_grad_probe_ms``: after the window, the program's jitted lambda
  pass ALONE on the booster's real scores, median of 5 (neither set-up's
  time nor the window's).

Faults of its own, beside the ones it inherits: ``pointwise``
(``objective=regression`` on the labels: the groups ignored) and
``no_truncation`` (``lambdarank_truncation_level`` = the longest query:
every pair counts), each a path of the program's that does other
mathematics.
"""

from __future__ import annotations

import time

import numpy as np

from .. import datagen, datagen_rank
from ..reference import rank_check
from . import train_window as tw

RANK_FACTS = ("rank_queries", "rank_doc_slots", "rank_pair_slots",
              "rank_pairs_visited", "rank_truncation", "rank_blocks")


def docs_range(config: dict):
    """``(lo, hi)`` of the configuration's ``query_docs`` (``"1-1251"``)."""
    lo, hi = str(config["query_docs"]).split("-")
    return int(lo), int(hi)


class Cell(tw.Cell):
    PARAM_FAULTS = dict(
        tw.Cell.PARAM_FAULTS,
        pointwise=lambda p, cfg: {"objective": "regression"},
        no_truncation=lambda p, cfg: {
            "lambdarank_truncation_level": docs_range(cfg)[1]})

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 fault: str = None):
        super().__init__(config, traffic, seed, devices, fault)
        self.queries = int(config["queries"])
        self._facts_read = False

    # -- set-up ------------------------------------------------------------
    def make_inputs(self) -> None:
        import jax

        import lightgbm_tpu as lgb

        t0 = time.perf_counter()
        table_seed = self.config.get("table_seed")
        lo, hi = docs_range(self.config)
        self.X, self.y, self.sizes = datagen_rank.mslr_like(
            self.rows, self.features, self.queries,
            self.seed if table_seed is None else int(table_seed), lo, hi)
        t_made = time.perf_counter()
        if table_seed is not None:
            datagen.reorder_columns(self.X, self.seed)
        t1 = time.perf_counter()
        self.dataset = lgb.Dataset(self.X, label=self.y, group=self.sizes,
                                   free_raw_data=True)
        self.dataset.construct()
        jax.block_until_ready(self.dataset.X_binned)
        self.counters.update(datagen_s=t1 - t0, reorder_s=t1 - t_made,
                             binning_s=time.perf_counter() - t1)

    def share_inputs(self, other: "Cell") -> None:
        super().share_inputs(other)
        self.sizes = other.sizes

    def _call(self) -> None:
        if not self._facts_read:
            self.read_layout_facts()
        super()._call()

    def read_layout_facts(self) -> None:
        """Before the first round: the facts the program notes for its
        round program, copied into the counters; none, no run."""
        from ..readers.program_span import snapshot

        self._facts_read = True
        if self.fault == "pointwise":   # no ranking objective, no layout
            return
        self.booster._fused_segment(self.rounds_per_call)
        facts = snapshot().get("facts", {})
        missing = [f for f in RANK_FACTS if "train." + f not in facts]
        if missing:
            raise SystemExit(
                "benchmark: the program notes no train."
                + ", train.".join(missing) + " for its round program: its "
                "query layout cannot be read, so the cell's counters "
                "cannot be filled: nothing was trained, no number is "
                "printed")
        for f in RANK_FACTS:
            self.counters[f] = facts["train." + f]

    # -- after the window --------------------------------------------------
    def release(self) -> None:
        self.probe_lambda_pass()
        super().release()

    def probe_lambda_pass(self) -> None:
        """The program's lambda pass alone (``Booster._group_grad_call``:
        the jitted pass and its operands) on the booster's real scores
        after the window's last round: median of 5, in milliseconds.  It
        runs after the window and after the memory's peak is read, so its
        seconds (``rank_grad_probe_s``, its program's build included) are
        in neither ``setup_s`` nor the window; a pass that fails fails the
        run."""
        import jax

        t_start = time.perf_counter()
        call = self.booster._group_grad_call()
        if call is None:            # a pointwise objective has no such pass
            return
        fn, args = call
        jax.block_until_ready(fn(*args))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            took.append(time.perf_counter() - t0)
        self.counters["rank_grad_probe_ms"] = 1000.0 * float(np.median(took))
        self.counters["rank_grad_probe_s"] = time.perf_counter() - t_start

    # -- the reference -----------------------------------------------------
    def init_score(self) -> float:
        return 0.0

    def reference_rounds(self, k: int) -> list:
        return rank_check.check_rounds(
            self.X, self.y, self.sizes, self.trees[:k], self.scores_after,
            self.program_init, self.config["reference"], seed=self.seed,
            split_nodes=self.split_nodes,
            order_leaves=self.order_leaves)["rounds"]

    def own_checks(self, rounds) -> list:
        if self.trees:
            leaves = self.leaf_counts()
            self.counters["leaves_least"] = min(leaves)
            self.counters["leaves_most"] = max(leaves)
        if rounds is not None:
            # read, not compared; the own_* are what ranking by the
            # program's stored scores hides
            for key in ("ndcg10", "leaves", "hessian_sum"):
                self.counters["reference_" + key] = [rd[key] for rd in rounds]
            for name in ("own_rank_flips", "own_grad_rows", "own_grad_gap"):
                self.counters[name] = [rd[name] for rd in rounds]
        return [("init_abs", abs(float(self.program_init)),
                 self.config["limits"].get("init_abs", 0.0))]
