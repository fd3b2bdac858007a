"""The counts behind ``mfu`` and ``agg_roofline`` need no more than the
work the algorithm must do, so neither share can pass 100%."""
import json

import pytest

from lib import counting, reference
from lib.cell import BENCH
from lib.peaks import chip_peaks

PEAKS = chip_peaks("TPU v5 lite")


def _config(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, reference.load_module(BENCH / "configs" / cfg["reference"])


def test_ssd_count_takes_the_cheapest_chunking():
    H, P, N, S = 64, 64, 128, 512
    got = counting.ssd_scan(S, H, P, N)
    for Q in (1, 16, 64, 128, 256, 512):
        assert got <= Q * N + H * P * Q + 4 * H * P * N + 2 * H * P * N / Q
    assert got >= 4 * H * P * N               # the two state contractions


def test_mamba2_forward_count_by_hand():
    hf, ref = _config("mamba2-1.3b")
    d, din, S = 2048, 4096, 512
    proj = 2 * (2 * d * din + 2 * d * 128 + d * 64 + din * d)
    head = 2 * d * 12570
    got = ref.forward_flops_per_token(hf, S, counting)
    assert got == proj + counting.ssd_scan(S, 64, 64, 128) + head


@pytest.mark.parametrize("name,seq", [("mamba2-1.3b", 512)])
def test_mfu_of_required_work_at_peak_is_at_most_100(name, seq):
    """An implementation that computes only the live steps' required
    products, each at the chip's peak, reads 100% and no more."""
    hf, ref = _config(name)
    live_steps, tokens = 37, seq
    flops = live_steps * tokens * counting.train_flops_per_token(
        ref.forward_flops_per_token(hf, seq, counting))
    seconds = flops / PEAKS["bf16_flops"]
    from lib.harness import Facts
    import importlib.util
    spec = importlib.util.spec_from_file_location("m", BENCH / "metrics" / "mfu.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    facts = Facts(space={}, window_ns=(0, 1), window_s=seconds, rounds=1,
                  chunks=1, host_chunk_s=[0.0], live_flops=flops, peaks=PEAKS,
                  agg_bytes_per_round=0.0, bench=BENCH)
    assert mod.read(facts) == pytest.approx(100.0)
    facts.window_s *= 1.5                     # any real run takes longer
    assert mod.read(facts) < 100.0


@pytest.mark.parametrize("n,s", [(2, 1), (8, 2), (128, 2)])
def test_agg_roofline_of_selected_rows_only_is_at_most_100(n, s):
    """A kernel that moves only the server row and the s selected rows of
    clients and inits, at the chip's HBM bandwidth, reads 100%; the
    streamed kernel, which reads all n rows, reads (2s+2)/(2n+2) of that."""
    D = 52_065_728
    need = counting.agg_bytes_per_round(D, s)
    assert need == (2 * s + 2) * D * 4
    from lib.harness import Facts
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "r", BENCH / "metrics" / "agg_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for moved, want in ((need, 100.0), ((2 * n + 2) * D * 4,
                                        100.0 * (2 * s + 2) / (2 * n + 2))):
        ms = 1e3 * moved / PEAKS["hbm_bytes_per_s"]

        class F(Facts):
            def metric(self, name):
                assert name == "agg_kernel_ms"
                return ms
        f = F(space={}, window_ns=(0, 1), window_s=1.0, rounds=1, chunks=1,
              host_chunk_s=[0.0], live_flops=0.0, peaks=PEAKS,
              agg_bytes_per_round=need, bench=BENCH)
        assert mod.read(f) == pytest.approx(want)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        chip_peaks("TPU v9 imaginary")
