"""Operation and byte counts against hand counts at small shapes."""
import pytest

from chipbench import flops

Z = {"d": 4, "h": 2, "kv": 1, "hd": 2, "ff": 8, "vocab": 10, "layers": 3}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_matmul_params_by_hand():
    # per layer: q 4*4, k 4*2, v 4*2, o 4*4, mlp 3*4*8; head 4*10
    assert flops.matmul_params(Z) == 3 * (16 + 8 + 8 + 16 + 96) + 40


def test_train_flops_per_token_by_hand():
    # 6 * 472 weights + 6 * 3 layers * 2 heads * 2 dims * (seq + 1)
    assert flops.train_flops_per_token(Z, seq=3) == 6 * 472 + 6 * 3 * 2 * 2 * 4


def test_flash_attention_by_hand():
    # b=1, h=2, kv=1, s=3, hd=2: query i meets i+1 keys -> 1+2+3 = 6 pairs
    # per head, 2 products of 2*hd operations each: 6 * 2 * 4 * 2 heads
    w = flops.flash_attention_fwd(1, 2, 1, 3, 2)
    assert w["flops"] == 6 * 2 * 4 * 2
    # q and o: 2*3*2 floats each; k and v: 3*2 each; lse: 2*3
    assert w["bytes"] == 4 * (12 + 12 + 6 + 6 + 6)


@pytest.mark.parametrize("work,seconds,bound", [
    ({"flops": 1000.0, "bytes": 10.0}, 10.0, "compute"),
    ({"flops": 10.0, "bytes": 1000.0}, 100.0, "memory"),
])
def test_least_time(work, seconds, bound):
    got = flops.least_time(work, PEAKS)
    assert got == {"seconds": seconds, "bound": bound}
