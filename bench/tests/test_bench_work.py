"""The work and bytes of each layer, the peaks table and the readers."""

import pytest

from bench import manifest, peaks, work
from bench.tests import tiny

V5E = "TPU v5 lite"


def test_clime_iteration_at_d1024():
    it = work.admm_iteration(1024, 1024)
    assert it.flops == pytest.approx(8.59e9, rel=1e-3)
    assert it.flops == 8 * 1024 ** 3
    assert it.bytes == 0


def test_solve_and_fit_counts():
    d, n, iters = 200, 1000, 600
    assert work.gram(n, d).flops == 2 * n * d * d
    solve = work.admm_solve(d, d, iters)
    assert solve.flops == iters * 8 * d ** 3
    assert solve.bytes == 4 * (2 * d * d + 2 * d * d)
    both = work.admm(d, iters)
    assert both.flops == iters * 8 * d * d * (d + 1)
    fit = work.fit(n, d, iters, rounds=3)
    assert fit.flops == (work.gram(n, d).flops + both.flops
                         + 3 * work.debias_round(d).flops)


def test_admm_is_compute_bound_on_v5e():
    p = peaks.peaks(V5E)
    least, bound = work.admm(1024, 600).least_seconds(p.bf16_flops,
                                                      p.hbm_bytes_per_s)
    assert bound == "compute"
    assert least == pytest.approx(600 * 8 * 1024 ** 2 * 1025 / 197e12)
    assert work.Work(1.0, 1e9).least_seconds(1e12, 1e9) == (1.0, "memory")


def test_peaks_lookup():
    p = peaks.peaks(V5E)
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _summary(layers, fits=2, window_s=1.0, chips=1):
    return {
        "fits": fits, "window_s": window_s, "chips": chips,
        "machines_per_chip": 1.0,
        "devices": {i: {"busy_s": 0.8 * window_s, "idle_s": 0.2 * window_s,
                        "layers": layers} for i in range(chips)},
        "peaks": peaks.peaks(V5E),
        "admm_work": work.admm(1024, 600),
        "fit_work": work.fit(512, 1024, 600, 1),
    }


READERS = ("device_idle_share", "fit_mfu", "gram_ms", "eigh_ms", "admm_ms",
           "admm_roofline", "collective_ms.fit4")


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_where_its_layer_is_absent(metric):
    read = manifest.load_reader(tiny.REPO, metric)
    empty = _summary({})
    empty["devices"] = {}
    value = read(empty)
    if metric == "fit_mfu":  # the whole fit needs no layer
        assert value > 0
    else:
        assert value is None
    if metric not in ("device_idle_share", "fit_mfu"):
        assert read(_summary({"other": 0.5})) is None


def test_readers_values():
    s = _summary({"gram": 0.002, "eigh": 0.06, "admm": 0.5,
                  "collective": 0.001}, fits=2, window_s=0.6)
    read = lambda m: manifest.load_reader(tiny.REPO, m)(s)
    assert read("device_idle_share") == pytest.approx(20.0)
    assert read("gram_ms") == pytest.approx(1.0)
    assert read("eigh_ms") == pytest.approx(30.0)
    assert read("admm_ms") == pytest.approx(250.0)
    assert read("collective_ms.fit4") == pytest.approx(0.5)
    least = 600 * 8 * 1024 ** 2 * 1025 / 197e12
    assert read("admm_roofline") == pytest.approx(100 * least / 0.25)
    fit_flops = work.fit(512, 1024, 600, 1).flops
    assert read("fit_mfu") == pytest.approx(100 * fit_flops / 0.3 / 197e12)


def test_roofline_cannot_pass_100_at_the_peak():
    """A device time equal to the least time reads exactly 100%."""
    p = peaks.peaks(V5E)
    least, _ = work.admm(1024, 600).least_seconds(p.bf16_flops,
                                                  p.hbm_bytes_per_s)
    s = _summary({"admm": least}, fits=1)
    assert manifest.load_reader(tiny.REPO, "admm_roofline")(s) == \
        pytest.approx(100.0)
