"""The comparison that decides ``correct`` catches what it must.

Each test drives a whole run of a tiny cell on the CPU with the chip check
skipped and the timed path replaced underneath the service: by the control
(the plain reference computed in bfloat16, the step below the
configuration's float32) and by each fault a serving cell can have. Every
one of them has to come out as not correct.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from harness import correctness, runner, spec


def _run(tiny_bench, tmp_path, cell, plan_wrap):
    root, bench = tiny_bench
    return runner.run(spec.load_cell(root, cell, bench), 4242, 2, False,
                      t_process=time.perf_counter(),
                      out_dir=str(tmp_path / "out"), require_tpu=False,
                      plan_wrap=plan_wrap)


def _answer_altered(base, index, cfg):
    """The best answer of every query names the next passage."""
    n_docs = cfg["n_passages"]

    def plan(q, m, f=None):
        r = base(q, m, f)
        ids = r.doc_ids.at[:, 0].set((r.doc_ids[:, 0] + 1) % n_docs)
        return type(r)(r.scores, ids)

    return plan


def _half_batch_left_out(base, index, cfg):
    """Only the first half of each batch is computed; the rest get the
    answers of the first half."""

    def plan(q, m, f=None):
        b = q.shape[0]
        h = max(1, b // 2)
        r = base(q[:h], m[:h], f)
        rows = jnp.arange(b) % h
        return type(r)(r.scores[rows], r.doc_ids[rows])

    return plan


def _best_answer_missed(base, index, cfg):
    """The best passage of every query is left out; the rest move up a
    rank and the last is named twice. Every served score is right."""

    def plan(q, m, f=None):
        r = base(q, m, f)

        def shift(a):
            return jnp.concatenate([a[:, 1:], a[:, -1:]], axis=1)

        return type(r)(shift(r.scores), shift(r.doc_ids))

    return plan


def test_control_is_not_correct(tiny_bench, tmp_path):
    result = _run(tiny_bench, tmp_path, "tiny.poisson", runner.control_plan)
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["failed"]["value"] == 0
    assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny.poisson", _answer_altered),
    ("tiny.bulk", _answer_altered),
    ("tiny.bulk", _half_batch_left_out),
    ("tiny.poisson", _best_answer_missed),
])
def test_fault_in_the_timed_path_is_not_correct(tiny_bench, tmp_path, cell,
                                                fault):
    result = _run(tiny_bench, tmp_path, cell, fault)
    assert result["correct"] is False, result["checks"]
    assert result["failed"] == 0


def test_unanswered_queries_are_not_correct(tiny_bench, tmp_path):
    def broken(base, index, cfg):
        launches = []

        def plan(q, m, f=None):
            launches.append(q.shape[0])
            if len(launches) > 4:          # after the four warm-up flushes
                raise RuntimeError("planted fault: the launch fails")
            return base(q, m, f)
        return plan

    result = _run(tiny_bench, tmp_path, "tiny.poisson", broken)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0


ENG = {"n_filter": 6, "n_docs": 4, "k": 2}
N_DOCS = 20


def _emvb(f, ci, e, tie_order):
    """EMVB's three cuts over candidates 0..n-1, the F ties at the first
    cut taken in ``tie_order``. -> the top-k ids."""
    ties_first = {int(p): r for r, p in enumerate(tie_order)}
    p3 = sorted(range(len(f)),
                key=lambda p: (-f[p], ties_first.get(p, 0)))[:ENG["n_filter"]]
    p4 = sorted(p3, key=lambda p: -ci[p])[:ENG["n_docs"]]
    return np.array(sorted(p4, key=lambda p: -e[p])[:ENG["k"]])


def _readings(f, ci, e, pool=16):
    """The reference's readings of one query over candidates 0..n-1."""
    n = len(f)
    pad = pool - n
    cand = np.concatenate([np.arange(n), np.full(pad, N_DOCS)])
    return {"n_cand": np.int32(n), "cand": cand,
            "f": np.concatenate([f, np.full(pad, -1)]),
            "ci": np.concatenate([ci, np.full(pad, -np.inf)]),
            "e_rows": np.arange(n), "e": np.asarray(e, np.float32)}


F = np.array([3, 3, 2, 2, 1, 1, 1, 1, 1, 0, 0, 0])
CI = np.array([9.0, 5.0, 6.0, 4.0, 3.0, 2.0, 1.0, 8.0, 7.0, 0.5, 0.4, 0.3])
E = np.array([1.0, 4.0, 3.0, 2.0, 0.5, 0.4, 0.3, 6.0, 5.0, 9.0, 9.5, 9.9])


@pytest.mark.parametrize("tie_order", [[4, 5, 6, 7, 8], [8, 7, 6, 5, 4],
                                       [7, 4, 8, 5, 6]])
def test_selection_gap_is_blind_to_the_order_of_ties(tie_order):
    served = _emvb(F, CI, E, tie_order)
    gaps, reasons = correctness.selection_gaps(
        [{k: v[None] for k, v in _readings(F, CI, E).items()}], served[None],
        ENG, N_DOCS)
    assert gaps.tolist() == [0.0] and reasons == []


def test_selection_gap_reads_a_wrong_selection():
    # 8 and 2 served: with the F tie 8 taken, phase 3 keeps 0, 8, 2 and
    # either 1 (Eq. 6 4.0, over 2's 3.0 by 1.0) or the tie 7 (Eq. 6 6.0)
    served = np.array([[8, 2]])
    refs = [{k: v[None] for k, v in _readings(F, CI, E).items()}]
    gaps, _ = correctness.selection_gaps(refs, served, ENG, N_DOCS)
    assert gaps[0] == pytest.approx(1.0, rel=0.02)
    # a passage that cannot pass the pre-filter, and a passage named twice
    for bad in ([[9, 7]], [[7, 7]]):
        gaps, reasons = correctness.selection_gaps(refs, np.array(bad),
                                                   ENG, N_DOCS)
        assert gaps.tolist() == [np.inf] and len(reasons) == 1
