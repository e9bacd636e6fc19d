"""Core analytics vs the paper's own published numbers (§III-§V)."""
import dataclasses
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    CPU_DDR, GPU_GDDR, LatencyTargets, LogNormalWorkload, EmpiricalWorkload,
    break_even, bottleneck, gamma_from_mix, iops_ssd_peak, normal_ssd,
    rho_max_for_targets, storage_next_ssd, thresholds, usable_iops,
)
from repro.core.constraints import tail_read_latency, mean_read_latency
from repro.core.economics import break_even_components
from repro.core.ssd_model import PSLC, TLC, rw_fractions


SSD = storage_next_ssd()


# ---------------------------------------------------------------------------
# §III-B / Table II: first-principles IOPS
# ---------------------------------------------------------------------------

class TestSsdModel:
    def test_paper_headline_iops(self):
        # "IOPS_SSD ~= 57M at 512B and ~= 11M at 4KB"
        assert float(iops_ssd_peak(SSD, 512)) == pytest.approx(57.4e6, rel=0.01)
        assert float(iops_ssd_peak(SSD, 4096)) == pytest.approx(11.1e6, rel=0.01)

    @pytest.mark.parametrize("n_ch,n_nand,tau_cmd,at512,at4k", [
        (16, 3, 200e-9, 39.4e6, 8.5e6),    # Table II pessimistic
        (20, 4, 150e-9, 57.4e6, 11.1e6),   # baseline
        (24, 5, 100e-9, 79.3e6, 13.8e6),   # optimistic
    ])
    def test_table2_sensitivity(self, n_ch, n_nand, tau_cmd, at512, at4k):
        cfg = dataclasses.replace(SSD, n_ch=n_ch, n_nand=n_nand,
                                  tau_cmd=tau_cmd)
        assert float(iops_ssd_peak(cfg, 512)) == pytest.approx(at512, rel=0.01)
        assert float(iops_ssd_peak(cfg, 4096)) == pytest.approx(at4k, rel=0.01)

    def test_iops_monotone_in_block_size(self):
        vals = [float(iops_ssd_peak(SSD, l)) for l in (512, 1024, 2048, 4096)]
        assert vals == sorted(vals, reverse=True)

    def test_nand_ordering(self):
        # SLC > pSLC > TLC at every block size (Fig. 3)
        for l in (512, 1024, 2048, 4096):
            slc = float(iops_ssd_peak(SSD, l))
            pslc = float(iops_ssd_peak(storage_next_ssd(PSLC), l))
            tlc = float(iops_ssd_peak(storage_next_ssd(TLC), l))
            assert slc > pslc > tlc

    def test_tlc_device_limited_flat(self):
        # TLC: long sense/program keeps the die the limiter at all sizes,
        # so IOPS varies only weakly with block size (Fig. 3 discussion).
        tlc = storage_next_ssd(TLC)
        v512 = float(iops_ssd_peak(tlc, 512))
        v4k = float(iops_ssd_peak(tlc, 4096))
        assert bottleneck(tlc, 512) == "nand_die"
        assert v512 / v4k < 1.6      # near-flat vs SLC's ~5.2x

    def test_normal_ssd_flat_below_4k(self):
        # 4KB-oriented ECC: sub-4KB requests cost a full codeword.
        nr = normal_ssd()
        assert float(iops_ssd_peak(nr, 512)) == pytest.approx(
            float(iops_ssd_peak(nr, 4096)), rel=1e-6)

    def test_read_only_exceeds_mixed(self):
        ro = float(iops_ssd_peak(SSD, 512, gamma_rw=float("inf")))
        mixed = float(iops_ssd_peak(SSD, 512, gamma_rw=9.0))
        heavy = float(iops_ssd_peak(SSD, 512, gamma_rw=1.0))
        assert ro > mixed > heavy

    def test_rw_fractions_sum(self):
        r, w, hf = rw_fractions(9.0, 3.0)
        assert float(r) + float(w) == pytest.approx(1.0)
        assert 0 < float(hf) <= 1.0
        r, w, hf = rw_fractions(float("inf"), 3.0)
        assert (float(r), float(w), float(hf)) == (1.0, 0.0, 1.0)

    def test_gamma_from_mix(self):
        assert gamma_from_mix(90, 10) == 9.0
        assert gamma_from_mix(100, 0) == float("inf")

    def test_cost_structure(self):
        # 20ch x 4 dies + ctrl 15 + ceil(40GB ftl / 3GB) DRAM dies
        assert SSD.n_s_dram == 14
        assert SSD.cost == pytest.approx(15 + 80 + 14)


# ---------------------------------------------------------------------------
# §III-C / Fig. 4: calibrated break-even
# ---------------------------------------------------------------------------

class TestEconomics:
    def test_fig4_cpu_anchors(self):
        # "~34s at 512B ... ~10s at 4KB" (CPU+DDR, SLC, Storage-Next)
        be512 = float(break_even(CPU_DDR, 512, SSD.cost,
                                 iops_ssd_peak(SSD, 512)))
        be4k = float(break_even(CPU_DDR, 4096, SSD.cost,
                                iops_ssd_peak(SSD, 4096)))
        assert be512 == pytest.approx(34.0, rel=0.1)
        assert be4k == pytest.approx(10.0, rel=0.15)

    def test_fig4_gpu_anchor_and_7x(self):
        cpu = float(break_even(CPU_DDR, 512, SSD.cost,
                               iops_ssd_peak(SSD, 512)))
        gpu = float(break_even(GPU_GDDR, 512, SSD.cost,
                               iops_ssd_peak(SSD, 512)))
        assert gpu == pytest.approx(5.0, rel=0.1)
        assert cpu / gpu == pytest.approx(7.0, rel=0.1)

    def test_seconds_not_minutes(self):
        # the paper's headline: thresholds collapse below the minute scale
        for host in (CPU_DDR, GPU_GDDR):
            for l in (512, 1024, 2048, 4096):
                be = float(break_even(host, l, SSD.cost,
                                      iops_ssd_peak(SSD, l)))
                assert be < 60.0

    def test_components_positive_and_sum(self):
        comps = break_even_components(CPU_DDR, 512, SSD.cost,
                                      iops_ssd_peak(SSD, 512))
        total = float(break_even(CPU_DDR, 512, SSD.cost,
                                 iops_ssd_peak(SSD, 512)))
        assert all(float(v) > 0 for v in comps.values())
        assert float(sum(comps.values())) == pytest.approx(total)

    def test_fig5a_host_budget_anchors(self):
        # CPU 512B: budget 40M -> ~83s, 100M -> ~47s (4 SSDs)
        peak = float(iops_ssd_peak(SSD, 512))
        for budget, expect in ((40e6, 83.0), (100e6, 47.0)):
            per = float(usable_iops(peak, 1.0, budget, 4))
            be = float(break_even(CPU_DDR, 512, SSD.cost, per))
            assert be == pytest.approx(expect, rel=0.1)

    def test_storage_next_beats_normal_small_blocks(self):
        for l in (512, 1024, 2048):
            sn = float(break_even(CPU_DDR, l, SSD.cost, iops_ssd_peak(SSD, l)))
            nr_ssd = normal_ssd()
            nr = float(break_even(CPU_DDR, l, nr_ssd.cost,
                                  iops_ssd_peak(nr_ssd, l)))
            assert sn < nr


# ---------------------------------------------------------------------------
# §IV / Table IV: M/D/1 constraints
# ---------------------------------------------------------------------------

class TestConstraints:
    @pytest.mark.parametrize("l_blk,tail_us,rho", [
        (512, 7, 0.70), (512, 9, 0.80), (512, 13, 0.90), (512, 85, 0.99),
        (4096, 16, 0.70), (4096, 44, 0.90), (4096, 418, 0.99),
    ])
    def test_table4_tiers(self, l_blk, tail_us, rho):
        peak = float(iops_ssd_peak(SSD, l_blk))
        got = float(rho_max_for_targets(
            LatencyTargets(tail=tail_us * 1e-6), SSD.n_ch, peak,
            SSD.nand.tau_sense))
        assert got == pytest.approx(rho, abs=0.05)

    def test_rho_roundtrip(self):
        # latency at rho_max equals the target (closed-form inverse)
        peak = float(iops_ssd_peak(SSD, 512))
        t = 13e-6
        rho = float(rho_max_for_targets(LatencyTargets(tail=t), SSD.n_ch,
                                        peak, SSD.nand.tau_sense))
        back = float(tail_read_latency(rho, SSD.n_ch, peak,
                                       SSD.nand.tau_sense, p=0.99))
        assert back == pytest.approx(t, rel=1e-6)

    def test_mean_constraint(self):
        peak = float(iops_ssd_peak(SSD, 512))
        rho = float(rho_max_for_targets(LatencyTargets(mean=6e-6), SSD.n_ch,
                                        peak, SSD.nand.tau_sense))
        back = float(mean_read_latency(rho, SSD.n_ch, peak,
                                       SSD.nand.tau_sense))
        assert back == pytest.approx(6e-6, rel=1e-6)

    def test_impossible_target_zero(self):
        peak = float(iops_ssd_peak(SSD, 512))
        rho = float(rho_max_for_targets(
            LatencyTargets(tail=1e-6),  # below tau_sense
            SSD.n_ch, peak, SSD.nand.tau_sense))
        assert rho == 0.0

    @given(st.floats(min_value=5.5e-6, max_value=1e-3),
           st.floats(min_value=5.5e-6, max_value=1e-3))
    @settings(max_examples=50, deadline=None)
    def test_rho_monotone_in_target(self, t1, t2):
        peak = float(iops_ssd_peak(SSD, 512))
        r1 = float(rho_max_for_targets(LatencyTargets(tail=t1), SSD.n_ch,
                                       peak, SSD.nand.tau_sense))
        r2 = float(rho_max_for_targets(LatencyTargets(tail=t2), SSD.n_ch,
                                       peak, SSD.nand.tau_sense))
        if t1 <= t2:
            assert r1 <= r2 + 1e-12
        else:
            assert r2 <= r1 + 1e-12

    def test_usable_iops_host_cap(self):
        assert float(usable_iops(57e6, 0.9, 100e6, 4)) == pytest.approx(25e6)
        assert float(usable_iops(10e6, 0.9, 100e6, 4)) == pytest.approx(9e6)


# ---------------------------------------------------------------------------
# §V: workload thresholds
# ---------------------------------------------------------------------------

class TestWorkload:
    def _wl(self, sigma=1.0, l_blk=512):
        # §V-B: 1e9 blocks, 200 GB/s total throughput
        return LogNormalWorkload.from_total_throughput(
            200e9, sigma=sigma, n_blk=1e9, l_blk=l_blk)

    def test_total_throughput_pinned(self):
        wl = self._wl()
        assert wl.total_throughput == pytest.approx(200e9, rel=1e-9)

    def test_psi_split_conserves(self):
        wl = self._wl()
        for T in (0.01, 0.1, 1.0, 10.0, 100.0):
            assert float(wl.psi_c(T) + wl.psi_d(T)) == pytest.approx(
                wl.total_throughput, rel=1e-9)

    def test_bw_use_decreasing(self):
        wl = self._wl()
        ts = np.logspace(-3, 3, 25)
        bws = [float(wl.dram_bw_use(t)) for t in ts]
        assert all(b1 >= b2 - 1e-3 for b1, b2 in zip(bws, bws[1:]))

    def test_threshold_inversions_roundtrip(self):
        wl = self._wl()
        # B >= 2*Theta: constraint holds for any T -> T_B = 0
        assert wl.bandwidth_threshold(540e9) == 0.0
        # Theta < B < 2*Theta: tight crossing
        t_b = wl.bandwidth_threshold(250e9)
        assert float(wl.dram_bw_use(t_b)) == pytest.approx(250e9, rel=1e-6)
        t_s = wl.ssd_threshold(50e9)
        assert float(wl.psi_d(t_s)) == pytest.approx(50e9, rel=1e-6)
        t_c = wl.capacity_threshold(64e9)
        assert float(wl.cached_bytes(t_c)) == pytest.approx(64e9, rel=1e-6)

    def test_infeasible_bandwidth(self):
        wl = self._wl()
        assert wl.bandwidth_threshold(100e9) == float("inf")  # < Theta

    def test_hit_rate_saturates(self):
        wl = self._wl()
        assert float(wl.hit_rate_for_capacity(0)) == 0.0
        assert float(wl.hit_rate_for_capacity(wl.total_bytes)) == 1.0
        mid = float(wl.hit_rate_for_capacity(wl.total_bytes / 2))
        assert 0.5 < mid < 1.0   # hot half carries > half the accesses

    @given(st.floats(min_value=0.3, max_value=2.0),
           st.integers(min_value=200, max_value=2000))
    @settings(max_examples=20, deadline=None)
    def test_lognormal_matches_empirical(self, sigma, n):
        """Closed forms agree with a sampled empirical profile."""
        wl = LogNormalWorkload.from_total_throughput(
            1e9, sigma=sigma, n_blk=float(n), l_blk=512)
        emp = EmpiricalWorkload(wl.sample_intervals(n, seed=7), 512)
        T = float(np.exp(wl.mu))  # median
        assert float(emp.cached_block_fraction(T)) == pytest.approx(
            float(wl.cached_block_fraction(T)), abs=0.1)
        assert float(emp.psi_c(T)) == pytest.approx(
            float(wl.psi_c(T)), rel=0.5)

    def test_empirical_threshold_semantics(self):
        emp = EmpiricalWorkload([1.0, 2.0, 4.0, 8.0], l_blk=1024)
        # Caching the two hottest blocks leaves psi_d = 1024*(1/4+1/8)
        t_s = emp.ssd_threshold(1024 * (1 / 4 + 1 / 8))
        assert t_s == pytest.approx(2.0)
        assert emp.capacity_threshold(2 * 1024) == pytest.approx(2.0)
        assert emp.capacity_threshold(100 * 1024) == float("inf")

    def test_thresholds_report(self):
        wl = self._wl()
        th = thresholds(wl, b_dram=540e9, b_ssd=4 * 512 * 25e6,
                        c_dram=256e9)
        assert th.t_v == max(th.t_b, th.t_s)
        assert isinstance(th.viable, bool)


def test_analytics_are_float64_without_enabling_x64():
    """The analytics keep float64 while JAX stays 32-bit for the whole
    process: kernels and served programs never see x64 dtypes."""
    import jax
    from repro.ssdsim.jaxsweep import analytic_iops_grid
    assert not jax.config.jax_enable_x64
    assert np.asarray(break_even(GPU_GDDR, 512, SSD.cost,
                                 iops_ssd_peak(SSD, 512))).dtype == np.float64
    grid = analytic_iops_grid(SSD, [512, 4096], [9.0, float("inf")])
    assert grid.dtype == np.float64 and grid.shape == (2, 2)
    assert grid[0, 0] == iops_ssd_peak(SSD, 512, 9.0)
