"""The FLOP and byte counts that the metric readers take from the
architecture module (``bench/models/mixtral.py``), against hand-worked
Mixtral-8x7B numbers (d 4096, 32 query and 8 K/V heads of 128, expert width
14336, top-2, vocabulary 32000, bf16)."""

import json
import pathlib

import pytest

from bench.lib import arch

ROOT = pathlib.Path(__file__).resolve().parents[2]


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def counts(c):
    """The architecture module of configuration ``c``, which holds its counts."""
    return arch.of(c, ROOT)


def test_train_model_flops_per_token():
    # Per layer: attention 4096*4096 + 2*4096*1024 + 4096*4096 = 41,943,040;
    # router 4096*4 = 16,384; two experts 2*3*4096*14336 = 352,321,536.
    # Head 4096*32000 = 131,072,000.  6 * (394,280,960 + 131,072,000)
    # = 3,152,117,760, plus causal attention 6 * 4096 * 4096 = 100,663,296.
    c = config("mixtral-8x7b-train-1chip")
    fpt = counts(c).flops_per_token(c, 4096)
    assert fpt == 3_252_781_056


def test_grouped_gemm_call_bounds():
    # 4096 tokens x top-2 = 8192 rows: 2 * 8192 * 4096 * 14336 FLOPs;
    # 4 experts' 4096 x 14336 weights once plus 8192 rows of 4096 in and
    # 14336 out, all bf16: 2 * (234,881,024 + 150,994,944) bytes.
    c = config("mixtral-8x7b-train-1chip")
    flops, nbytes = counts(c).call_bounds(c, 4096)
    assert flops == 962_072_674_304
    assert nbytes == 771_751_936
    # On a v5e (197 TFLOP/s, 819 GB/s) the FLOPs bound the call: 4.88 ms.
    assert flops / 197e12 == pytest.approx(4.8836e-3, rel=1e-4)
    assert nbytes / 819e9 < flops / 197e12


def test_serve_tick_bounds():
    # Two layers, all 8 experts.  Active per layer: 41,943,040 attention
    # + 32,768 router + 352,321,536 experts = 394,297,344.  64 decoded
    # tokens: 64 * 2 * (2 * 394,297,344 + 131,072,000) = 117,717,336,064,
    # plus 4 * 4096 * 2 layers * 64,000 attended positions = 2,097,152,000.
    # Held bytes: per layer (41,943,040 + 8*3*4096*14336) * 2 + router
    # 4096*8*4 = 2,902,589,440; two layers plus the 262,144,000-byte head =
    # 6,067,322,880; K/V read: 64,000 positions * 2 layers * 2 * 8 * 128 * 2.
    c = config("mixtral-8x7b-serve-1chip")
    flops, nbytes = counts(c).tick_bounds(c, 64, 64_000, [])
    assert flops == 119_814_488_064
    assert nbytes == 6_067_322_880 + 524_288_000
    # A 256-token prompt adds 2*256*2*394,297,344 + 2*4096*32000
    # + 2*4096*2*256^2 FLOPs.
    flops_p, _ = counts(c).tick_bounds(c, 64, 64_000, [256])
    assert flops_p - flops == 403_760_480_256 + 262_144_000 + 1_073_741_824


def test_peaks_are_keyed_by_device_kind():
    from bench.lib import device

    v5e = device.peaks(ROOT, "TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks(ROOT, "TPU v9 imaginary")
