"""Shared by the benchmark's CPU tests (a module of its own name: two
``conftest`` modules cannot both be imported by name)."""

import json
import os

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "source": "a tiny stand-in for the CPU tests of the harness",
    "rows": 6000, "features": 28,
    "params": {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
               "max_bin": 255, "min_data_in_leaf": 0,
               "min_sum_hessian_in_leaf": 10.0, "lambda_l2": 0.0,
               "hist_dtype": "f32", "verbosity": -1},
    "precision": "float32 histograms (hi/lo split), so the control is bf16",
    "reference": {"learning_rate": 0.1, "lambda_l2": 0.0, "num_leaves": 15,
                  "max_bin": 255, "min_sum_hessian_in_leaf": 10.0},
    "control": {"params": {"hist_dtype": "bf16"}},
    "limits": {"leaves_off": 0, "split_gain_short": 0.02, "order_excess": 0.05,
               "leaf_value_worst": 1e-4,
               "leaf_count_off": 0, "score_abs": 1e-5,
               "final_score_abs": 1e-5},
    "reduced": [], "assumed": [],
}


# 6,000 documents x 28 columns in 80 queries of 1 to 400; lambdarank's
# hessians are small (about 2 a query), so a leaf is asked for 1 of them
TINY_RANK = {
    "source": "a tiny stand-in for the CPU tests of the ranking kind",
    "rows": 6000, "features": 28, "queries": 80, "query_docs": "1-400",
    "params": {"objective": "lambdarank", "num_leaves": 15,
               "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 0,
               "min_sum_hessian_in_leaf": 1.0, "lambda_l2": 0.0,
               "lambdarank_truncation_level": 30, "lambdarank_norm": True,
               "sigmoid": 1.0, "hist_dtype": "f32", "verbosity": -1},
    "precision": "float32 histograms (hi/lo split), so the control is bf16",
    "reference": {"learning_rate": 0.1, "lambda_l2": 0.0, "num_leaves": 15,
                  "max_bin": 255, "min_sum_hessian_in_leaf": 1.0,
                  "sigmoid": 1.0, "lambdarank_truncation_level": 30,
                  "lambdarank_norm": True},
    "control": {"params": {"hist_dtype": "bf16"}},
    "limits": {"leaves_off": 0, "split_gain_short": 0.05,
               "order_excess": 0.1,
               "leaf_value_worst": 1e-4, "leaf_count_off": 0,
               "score_abs": 1e-5, "final_score_abs": 1e-5, "init_abs": 0.0},
    "reduced": [], "assumed": [],
}


def order_faults(doc: dict) -> list:
    """What breaks the order of ``BENCHMARK.json``'s metric lists: a name
    that is no cell, a cell listed twice, a list out of the order of the
    top-level ``workloads`` (a cell is appended after the cells before it,
    and what was listed before still is, in its order)."""
    place = {w["name"]: i for i, w in enumerate(doc["workloads"])}
    faults = []
    for m in doc["end_to_end"] + doc["per_layer"]:
        listed = m.get("workloads", [])
        unknown = [c for c in listed if c not in place]
        if unknown:
            faults.append((m["name"], "no such cell", unknown))
        elif len(set(listed)) < len(listed):
            faults.append((m["name"], "listed twice", listed))
        elif [place[c] for c in listed] != sorted(place[c] for c in listed):
            faults.append((m["name"], "out of order", listed))
    return faults


class BenchCopy:
    def __init__(self, root):
        self.root = root

    def add(self, files=None, **entries):
        """Write NEW files and append entries to ``BENCHMARK.json``; a file
        that is already there is a failure of the test."""
        for rel, content in (files or {}).items():
            path = self.root / rel
            assert not path.exists(), f"{rel} is already there"
            path.write_text(content if isinstance(content, str)
                            else json.dumps(content))
        doc = json.loads((self.root / "BENCHMARK.json").read_text())
        for key, new in entries.items():
            doc[key].extend(new)
        (self.root / "BENCHMARK.json").write_text(json.dumps(doc))

    def add_cell(self, name, config, traffic="train-window", like=None):
        """A configuration and its cell on one chip, added as data: new
        files and appended entries.  The cell is appended to the metric
        lists that name the cell ``like`` (``"every"``: to every list;
        ``None``: to ``train_rows_rounds_per_s``'s alone)."""
        cell = f"{name}.train"
        self.add(
            files={f"benchmark/configs/{name}.json": config},
            configs=[{"name": name, "source": config["source"],
                      "reduced": config["reduced"],
                      "file": f"benchmark/configs/{name}.json",
                      "why": "tiny"}],
            workloads=[{"name": cell, "config": name, "traffic": traffic,
                        "chips": 1, "why": "tiny"}])
        doc = json.loads((self.root / "BENCHMARK.json").read_text())
        for m in doc["end_to_end"] + doc["per_layer"]:
            listed = m.get("workloads")
            if listed is not None and (
                    like == "every" or like in listed or (
                        like is None
                        and m["name"] == "train_rows_rounds_per_s")):
                listed.append(cell)
        (self.root / "BENCHMARK.json").write_text(json.dumps(doc))
        return cell

    def add_tiny_cell(self, name="tiny", config=None):
        """A tiny configuration and its training cell, which reports the
        end-to-end metrics of the training cells and per-layer metrics of
        its own choosing (none by default)."""
        return self.add_cell(name, config or TINY_CONFIG)

    def run(self, capsys, workload, seed=7, seconds=0.3, trace=0,
            fault=None):
        """``(result, stderr)`` of one run in this process; ``fault``: a
        fault of the kind's ``Cell``, planted in this run alone."""
        from benchmark import run

        capsys.readouterr()
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_tpu=False, root=str(self.root), fault=fault)
        assert rc == 0
        captured = capsys.readouterr()
        lines = [ln for ln in captured.out.splitlines() if ln.strip()]
        return json.loads(lines[-1]), captured.err


def grow_plain(X, g, h, edges, num_leaves, lam=0.0, min_hess=0.0,
               features=None, order="best"):
    """A plain leaf-wise grower for the tests of the reference: a
    LightGBM-style ``tree_structure`` grown on ``edges`` as candidate
    thresholds, splitting the leaf with the largest gain first
    (``order="oldest"``: level by level instead; ``features``: the only
    features the scan may use)."""
    from benchmark.reference import gbdt_check as ref

    feats = list(range(X.shape[1])) if features is None else list(features)

    def best(rows):
        G, H = g[rows].sum(), h[rows].sum()
        out = (-np.inf, None, None)
        for f in feats:
            e = edges[f]
            code = np.searchsorted(e, X[rows, f].astype(np.float64))
            GL, HL, CL = (np.cumsum(np.bincount(
                code, weights=w, minlength=len(e) + 1))[:-1]
                for w in (g[rows], h[rows], None))
            ok = np.flatnonzero((HL >= min_hess) & (H - HL >= min_hess)
                                & (CL > 0) & (CL < len(rows)))
            if ok.size:
                gains = ref.gain_of(GL[ok], HL[ok], G, H, lam)
                k = int(gains.argmax())
                if gains[k] > out[0]:
                    out = (float(gains[k]), f, float(e[ok[k]]))
        return out

    rows0 = np.arange(X.shape[0])
    nodes, leaves = [{"rows": rows0, "best": best(rows0)}], [0]
    while len(leaves) < num_leaves:
        open_ = [i for i in leaves if np.isfinite(nodes[i]["best"][0])]
        if not open_:
            break
        i = (max(open_, key=lambda j: nodes[j]["best"][0])
             if order == "best" else min(open_))
        _, f, t = nodes[i]["best"]
        rows = nodes[i]["rows"]
        left = X[rows, f].astype(np.float64) <= t
        for part in (rows[left], rows[~left]):
            nodes.append({"rows": part, "best": best(part)})
        nodes[i].update(split=(f, t), kids=(len(nodes) - 2, len(nodes) - 1))
        leaves.remove(i)
        leaves += [len(nodes) - 2, len(nodes) - 1]

    def dump(i):
        nd = nodes[i]
        r = nd["rows"]
        if "kids" not in nd:
            return {"leaf_value": float(-g[r].sum() / (h[r].sum() + lam)),
                    "leaf_count": len(r)}
        return {"split_feature": nd["split"][0], "threshold": nd["split"][1],
                "decision_type": "<=", "internal_count": len(r),
                "left_child": dump(nd["kids"][0]),
                "right_child": dump(nd["kids"][1])}

    return dump(0)
