"""The flash cost functions against hand-worked shapes."""

import pytest

from benchmark.kernels import flash_cost

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_forward_one_head_by_hand():
    # B=1, H=1, T=4, D=2, causal: 4*4/2 = 8 live pairs, two matmuls of
    # 2*D = 4 operations each per pair -> 64 operations.
    cost = flash_cost.forward((1, 1, 4, 2), dtype_bytes=2, causal=True)
    assert cost["ops"] == 64
    # q, k, v, o: 4 arrays of 1*1*4*2 bf16 = 16 bytes each, plus 4 float32
    # log-sum-exp values.
    assert cost["bytes"] == 4 * 16 + 16


def test_backward_is_five_matmuls():
    fwd = flash_cost.forward((3, 25, 1024, 64))
    bwd = flash_cost.backward((3, 25, 1024, 64))
    assert bwd["ops"] == pytest.approx(2.5 * fwd["ops"])
    # 3*25*1024*1024/2 pairs * 4*64 operations
    assert fwd["ops"] == 3 * 25 * 1024 * 1024 / 2 * 256


def test_backward_kernels_share_the_five_matmuls():
    shape = (3, 25, 1024, 64)
    whole = flash_cost.backward(shape)
    dq, dkv = flash_cost.backward_dq(shape), flash_cost.backward_dkv(shape)
    assert dq["ops"] + dkv["ops"] == pytest.approx(whole["ops"])
    assert dq["ops"] / dkv["ops"] == pytest.approx(3 / 4)
    io = 3 * 25 * 1024 * 64 * 2
    assert dq["bytes"] == 5 * io + 2 * 3 * 25 * 1024 * 4
    assert dkv["bytes"] == 6 * io + 2 * 3 * 25 * 1024 * 4


def test_not_causal_doubles_the_pairs():
    assert flash_cost.forward((2, 4, 128, 64), causal=False)["ops"] == \
        2 * flash_cost.forward((2, 4, 128, 64), causal=True)["ops"]


def test_roofline_names_the_bound():
    # T=1024, D=64: 2*1024*64/2... intensity is hundreds of operations a
    # byte, far above 197e12/819e9 = 240? Worked: ops = B*H*T*T/2*4*D,
    # bytes ~ B*H*T*D*8 -> ops/bytes = T/4 = 256 > 240: compute bound.
    r = flash_cost.roofline_seconds(
        flash_cost.forward((1, 1, 1024, 64)), PEAKS)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(1024 * 1024 / 2 * 256 / 197e12)
    r = flash_cost.roofline_seconds(
        flash_cost.forward((1, 1, 128, 64)), PEAKS)
    assert r["bound"] == "memory"


def test_kernel_events_are_told_apart_by_signature():
    from benchmark.layer_metrics import _flash
    fwd = ('%pallas_call.9 = (bf16[288,1024,64]{2,1,0}, f32[288,1024,1]'
           '{2,1,0}) custom-call(bf16[288,1024,64]{2,1,0} %a, bf16[288,1024,'
           '64]{2,1,0} %b, bf16[288,1024,64]{2,1,0} %c), '
           'custom_call_target="tpu_custom_call", operand_layout=...')
    six = ('bf16[288,1024,64]{2,1,0} %q, bf16[288,1024,64]{2,1,0} %k, '
           'bf16[288,1024,64]{2,1,0} %v, bf16[288,1024,64]{2,1,0} %do, '
           'f32[288,1024,1]{2,1,0} %lse, f32[288,1024,1]{2,1,0} %delta')
    dq = ('%pallas_call.10 = bf16[288,1024,64]{2,1,0} custom-call(' + six
          + '), custom_call_target="tpu_custom_call"')
    dkv = ('%pallas_call.11 = (bf16[288,1024,64]{2,1,0}, bf16[288,1024,64]'
           '{2,1,0}) custom-call(' + six
           + '), custom_call_target="tpu_custom_call"')
    other = ('%custom-call.2 = bf16[768,2304]{1,0} custom-call(bf16[192,2304]'
             '{1,0} %s), custom_call_target="ConcatBitcast"')
    assert [_flash.is_flash(t) for t in (fwd, dq, dkv, other)] == \
        [True, True, True, False]
    assert _flash.parse(fwd) == ("forward", [288, 1024, 64], 2)
    assert _flash.parse(dq)[0] == "backward_dq"
    assert _flash.parse(dkv)[0] == "backward_dkv"
