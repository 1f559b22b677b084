"""work.py against counts worked by hand for Mistral-7B-v0.3."""
import pytest

from benchmark import manifest, work

CFG = manifest.cell(manifest.load(), 'mistral7b-serve.chat')['config']


def test_parameter_counts():
    # wq 4096*4096, wk and wv 4096*1024 each, wo 4096*4096,
    # gate, up, down 4096*14336 each
    per_layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
    assert work.layer_matmul_params(CFG) == per_layer == 218_103_808
    assert work.head_params(CFG) == 134_217_728
    assert work.total_params(CFG) == (32 * (per_layer + 8192)
                                      + 2 * 134_217_728 + 4096)
    assert round(work.total_params(CFG) / 1e9, 2) == 7.25


def test_bytes():
    # K and V, 32 layers, 8 heads of 128, bf16
    assert work.kv_bytes_per_token(CFG) == 2 * 32 * 8 * 128 * 2 == 131_072
    assert work.weight_stream_bytes(CFG) == 32 * 218_103_808 + 134_217_728


def test_forward_flops_of_one_decode_token():
    # 2 flops a parameter in the layers and the head, plus attention over
    # 1000 keys: QK^T and PV are 2*128 each a head and key, 32 heads,
    # 32 layers
    want = (2 * 32 * 218_103_808 + 2 * 134_217_728
            + 4 * 32 * 128 * 32 * 1000)
    assert work.forward_flops(CFG, 1, 1000, 1) == want
    assert work.train_flops_per_token(CFG, 4096) == pytest.approx(
        3 * (2 * 32 * 218_103_808 + 2 * 134_217_728
             + 4 * 32 * 128 * 32 * 2048.5))


def test_paged_decode_work_reads_whole_pages():
    flops, bytes_ = work.paged_decode_work(CFG, [65, 64], page=64)
    assert flops == 4 * 32 * 128 * 32 * 129
    # 65 keys are two pages, 64 are one: 192 tokens of K and V, plus q
    # and the output, 32 heads of 128 in bf16, in 32 layers, two tokens
    assert bytes_ == 192 * 131_072 + 2 * 32 * 128 * 2 * 32 * 2


def test_paged_prefill_work_is_causal_inside_the_chunk():
    flops, bytes_ = work.paged_prefill_work(CFG, [(256, 512)])
    assert flops == 4 * 32 * 128 * 32 * (256 * 512 + 256 * 257 / 2)
    assert bytes_ == 768 * 131_072 + 2 * 256 * 32 * 128 * 2 * 32


def test_roofline_share_names_its_bound():
    peak = work.peaks('TPU v5 lite')
    assert peak['bf16_flops_per_s'] == 197e12
    assert peak['hbm_bytes_per_s'] == 819e9 and peak['source']
    r = work.roofline_share(197e12, 1.0, 2.0, peak)
    assert r == {'percent': 50.0, 'bound': 'compute'}
    assert work.roofline_share(1.0, 819e9, 4.0, peak) == {
        'percent': 25.0, 'bound': 'memory'}


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match='no published peaks'):
        work.peaks('cpu')
