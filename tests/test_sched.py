"""Scheduler: cost model sanity, knapsack optimality vs brute force, DTM,
job planner (Alg. 2), the Thm 6.1 AR bound, and baseline orderings that
reproduce the paper's qualitative results (PLoRA < MinGPU < MaxGPU)."""
import numpy as np
import pytest
from _hyp import given, settings, st  # guarded hypothesis import (skips sans hypothesis)

from repro.configs.base import LoraConfig, default_search_space, get_config
from repro.sched.cost_model import (
    A10_24G,
    A100_40G,
    TPU_V5E,
    CostModel,
    active_param_count,
    lora_param_count,
    model_param_count,
    tpu_prior,
)
from repro.sched.dtm import dtm
from repro.sched.knapsack import brute_force, solve_pack
from repro.sched.planner import (
    max_gpu_schedule,
    min_gpu_schedule,
    plan,
    sequential_plora_schedule,
)

CFG7B = get_config("qwen25-7b")
SEQ = 1024
STEPS = 100


@pytest.fixture(scope="module")
def cm():
    return CostModel(CFG7B, A100_40G)


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------


def test_param_counts_plausible(cm):
    n = model_param_count(CFG7B)
    assert 6e9 < n < 9e9, n  # "7B"
    a = active_param_count(CFG7B)
    assert a == n  # dense model
    moe = get_config("qwen3-moe-30b-a3b")
    assert active_param_count(moe) < 0.25 * model_param_count(moe)


def test_lora_param_fraction(cm):
    """Paper §2.1: rank-64 adapter on Qwen-2.5-7B updates ~3.4% of params."""
    frac = lora_param_count(CFG7B, 64) / model_param_count(CFG7B)
    assert 0.01 < frac < 0.06, frac


def test_memory_monotone_in_pack_size(cm):
    c = LoraConfig(rank=32, alpha=32, batch_size=1, seq_len=SEQ)
    m1 = cm.job_mem_bytes([c], 1, SEQ)
    m2 = cm.job_mem_bytes([c] * 4, 1, SEQ)
    assert m2 > m1
    # paper §3.2: one adapter ~18.2 GB, two ~20.4 GB on A100-40G => packing
    # the base dominates; marginal adapter cost small
    marginal = (m2 - m1) / 3
    assert marginal < 0.25 * m1


def test_paper_memory_scale(cm):
    """Single rank-64 adapter on 7B fits a 40 GB A100 with slack (paper
    reports 18.2 GB at bs=1 short-seq; our act model is coarser but must fit)."""
    c = LoraConfig(rank=64, alpha=64, batch_size=1, seq_len=SEQ)
    m = cm.job_mem_bytes([c], 1, SEQ)
    assert m < 0.9 * 40e9, m / 1e9


def test_iter_time_decreasing_in_devices(cm):
    c = LoraConfig(rank=32, alpha=32, batch_size=8, seq_len=SEQ)
    times = [cm.iter_time([c] * 8, d, SEQ) for d in (1, 2, 4, 8)]
    assert times[0] > times[-1]


def test_throughput_increases_with_packing(cm):
    """The paper's core observation: at bs=1 on short (GLUE-scale) sequences
    the device is so underutilized that packing N adapters raises LoRA
    throughput nearly Nx."""
    c = LoraConfig(rank=32, alpha=32, batch_size=1, seq_len=128)
    t1 = cm.throughput([c], 1, 128)
    t8 = cm.throughput([c] * 8, 1, 128)
    assert t8 > 4.0 * t1, (t1, t8)


def test_paper_anchor_bs1_to_bs8():
    """§5.1: iteration time grows only ~10% from bs 1 -> 8 (short seqs)."""
    cm = CostModel(CFG7B, A100_40G)
    c1 = LoraConfig(rank=32, alpha=32, batch_size=1, seq_len=64)
    c8 = LoraConfig(rank=32, alpha=32, batch_size=8, seq_len=64)
    ratio = cm.iter_time([c8], 1, 64) / cm.iter_time([c1], 1, 64)
    assert 1.0 < ratio < 1.35, ratio


def test_paper_anchor_naive_8pack():
    """§5.1: naive sequential 8-pack is ~3.6x slower than a single adapter."""
    cm = CostModel(CFG7B, A100_40G)
    c = LoraConfig(rank=32, alpha=32, batch_size=1, seq_len=64)
    ratio = cm.iter_time_sequential([c] * 8, 1, 64) / cm.iter_time([c], 1, 64)
    assert 2.5 < ratio < 4.5, ratio


def test_min_degree(cm):
    c = LoraConfig(rank=32, alpha=32, batch_size=1, seq_len=SEQ)
    assert cm.min_degree([c], SEQ) == 1
    cm32 = CostModel(get_config("command-r-35b"), A100_40G)
    assert cm32.min_degree([c], SEQ) >= 2  # 35B needs >1 40GB GPU


# ---------------------------------------------------------------------------
# Knapsack / F(D, K)
# ---------------------------------------------------------------------------


def test_solve_pack_beats_or_matches_brute_force_throughput(cm):
    configs = default_search_space(10, SEQ)
    got = solve_pack(cm, configs, 1, SEQ)
    want = brute_force(cm, configs, 1, SEQ)
    assert got is not None and want is not None
    # same throughput up to the additive-surrogate gap (must be >= 90% opt)
    assert got[1] >= 0.90 * want[1], (got[1], want[1])


def test_solve_pack_respects_memory(cm):
    configs = default_search_space(30, SEQ)
    res = solve_pack(cm, configs, 1, SEQ)
    assert res is not None
    sel = [configs[i] for i in res[0]]
    assert cm.fits(sel, 1, SEQ)


def test_solve_pack_none_when_base_doesnt_fit():
    cm35 = CostModel(get_config("command-r-35b"), A100_40G)
    res = solve_pack(cm35, default_search_space(5, SEQ), 1, SEQ)
    assert res is None  # 35B model cannot fit a single 40G device


@settings(max_examples=10, deadline=None)
@given(n=st.integers(3, 10), d=st.sampled_from([1, 2, 4]))
def test_solve_pack_feasibility_property(n, d):
    cm = CostModel(CFG7B, A100_40G)
    configs = default_search_space(n, SEQ)
    res = solve_pack(cm, configs, d, SEQ)
    if res is not None:
        sel = [configs[i] for i in res[0]]
        assert cm.fits(sel, d, SEQ)
        assert len(set(res[0])) == len(res[0])  # no duplicates


# ---------------------------------------------------------------------------
# DTM (Alg. 1) + planner (Alg. 2)
# ---------------------------------------------------------------------------


def test_dtm_covers_all_or_uses_all_devices(cm):
    configs = default_search_space(24, SEQ)
    res = dtm(cm, configs, 8, SEQ, STEPS)
    used = sum(j.degree for j in res.jobs)
    covered = {i for j in res.jobs for i in j.config_ids}
    assert used <= 8
    assert covered <= set(range(24))
    assert covered  # something scheduled


def test_dtm_degrees_are_powers_of_two(cm):
    configs = default_search_space(16, SEQ)
    res = dtm(cm, configs, 8, SEQ, STEPS)
    for j in res.jobs:
        assert j.degree & (j.degree - 1) == 0


def test_planner_schedules_every_config(cm):
    configs = default_search_space(40, SEQ)
    sched = plan(cm, configs, 8, SEQ, STEPS)
    covered = sorted(i for j in sched.jobs for i in j.config_ids)
    assert covered == list(range(40))
    # each config exactly once (paper Eq 3)
    assert len(covered) == len(set(covered))


def test_planner_never_oversubscribes(cm):
    from repro.sched.engine import ExecutionEngine

    configs = default_search_space(40, SEQ)
    sched = plan(cm, configs, 8, SEQ, STEPS)
    ExecutionEngine(cm, 8).simulate(sched)  # raises on oversubscription


def test_ar_bound_in_paper_range(cm):
    """Paper: AR between 1.05 and 1.14 in practice; bound must be >= 1 and
    small for the 120-config space."""
    configs = default_search_space(120, SEQ)
    sched = plan(cm, configs, 8, SEQ, STEPS)
    ar = sched.ar()
    assert 1.0 <= ar <= 1.25, ar


def test_makespan_ordering_plora_min_max(cm):
    """Fig. 4 qualitative: PLoRA < MinGPU < MaxGPU."""
    configs = default_search_space(60, SEQ)
    s_p = plan(cm, configs, 8, SEQ, STEPS)
    s_min = min_gpu_schedule(cm, configs, 8, SEQ, STEPS)
    s_max = max_gpu_schedule(cm, configs, 8, SEQ, STEPS)
    assert s_p.makespan < s_min.makespan < s_max.makespan


def test_sequential_plora_between(cm):
    """Fig. 6: Sequential PLoRA (planner only, no packed kernels) sits
    between MinGPU and full PLoRA on short-seq (paper-regime) workloads."""
    seq = 128
    configs = default_search_space(40, seq)
    s_p = plan(cm, configs, 8, seq, STEPS)
    s_seq = sequential_plora_schedule(cm, configs, 8, seq, STEPS)
    s_min = min_gpu_schedule(cm, configs, 8, seq, STEPS)
    assert s_p.makespan < s_seq.makespan < s_min.makespan


@pytest.mark.parametrize("hw", [A100_40G, A10_24G, TPU_V5E])
def test_planner_works_across_hardware(hw):
    cm = CostModel(get_config("qwen25-7b"), hw)
    configs = default_search_space(16, SEQ)
    if cm.min_degree([configs[0]], SEQ) is None:
        pytest.skip("base model does not fit this hardware pool")
    sched = plan(cm, configs, min(hw.n_devices, 8), SEQ, STEPS)
    assert sched.makespan > 0
    assert sorted(i for j in sched.jobs for i in j.config_ids) == list(range(16))


def test_calibration_scales_time(cm):
    c = LoraConfig(rank=32, alpha=32, batch_size=1, seq_len=SEQ)
    cm2 = CostModel(CFG7B, A100_40G)
    t_pred = cm2.iter_time([c], 1, SEQ)
    cm2.calibrate(measured_iter_time=2 * t_pred, configs=[c], d=1, seq=SEQ)
    t_new = cm2.iter_time([c], 1, SEQ)
    np.testing.assert_allclose(t_new, 2 * t_pred, rtol=1e-6)


def test_tpu_prior_by_device_kind():
    """A v5e gets its own prior; a TPU without one is an error, never
    another chip's preset."""
    assert tpu_prior("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="TPU v4"):
        tpu_prior("TPU v4")
