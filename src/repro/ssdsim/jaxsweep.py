"""Vectorized analytic sweeps over the first-principles model — used by
the sensitivity benchmarks to sweep large parameter grids cheaply and by
tests to cross-check the event simulator trends.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from ..core.ssd_model import SsdConfig, iops_ssd_peak


def analytic_iops_grid(cfg: SsdConfig, l_blks: Sequence[int],
                       gammas: Sequence[float], phi_wa: float = 3.0):
    """IOPS over the (block size x read:write ratio) grid.

    Returns array of shape (len(l_blks), len(gammas)).
    """
    ls = np.asarray(l_blks, np.float64)[:, None]
    gs = np.asarray(gammas, np.float64)[None, :]
    return iops_ssd_peak(cfg, ls, gs, phi_wa)


def analytic_channel_bw_sweep(cfg: SsdConfig, l_blk: int,
                              bws: Sequence[float], gamma: float = 9.0,
                              phi_wa: float = 3.0):
    """IOPS as channel bandwidth scales (paper Fig. 7c trend)."""
    out = []
    for bw in bws:
        c = dataclasses.replace(cfg, b_ch=float(bw))
        out.append(float(iops_ssd_peak(c, l_blk, gamma, phi_wa)))
    return np.asarray(out)
