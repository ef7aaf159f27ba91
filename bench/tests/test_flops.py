"""FLOP and byte functions against counts made by hand."""

from bench import flops, harness
from bench.reference import dense

# mistral-nemo-12b's published widths, 4 layers
K = dense.dims({"hidden_size": 5120, "num_hidden_layers": 4,
                "num_attention_heads": 32, "num_key_value_heads": 8,
                "head_dim": 128, "intermediate_size": 14336,
                "vocab_size": 131072, "rope_theta": 1e6,
                "rms_norm_eps": 1e-5})


def reader(name):
    return harness.load_module(harness.BENCH_DIR / "layer_metrics"
                               / f"{name}.py")


def test_dims():
    assert (K["D"], K["H"], K["KV"], K["hd"], K["F"], K["V"], K["L"]) == \
        (5120, 32, 8, 128, 14336, 131072, 4)


def test_layer_matmul_by_hand():
    # q 5120x4096, k and v 5120x1024 each, o 4096x5120, three 5120x14336
    params = 5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120 + 3 * 5120 * 14336
    assert params == 272_629_760
    assert flops.layer_matmul(K) == 2 * 272_629_760
    assert flops.head(K) == 2 * 5120 * 131072


def test_decode_and_prefill_by_hand():
    # one token at 1000 keys: 4 layers x (matmuls + 4*32*128*1000) + head
    want = 4 * (2 * 272_629_760 + 4 * 32 * 128 * 1000) + 2 * 5120 * 131072
    assert flops.decode_token(K, 1000) == want
    # a 3-token prompt attends 1 + 2 + 3 keys
    want = 4 * (3 * 2 * 272_629_760 + 4 * 32 * 128 * 6) + 2 * 5120 * 131072
    assert flops.prefill(K, 3) == want


def test_decode_kernel_work_by_hand():
    f, b = reader("paged_decode_roofline").kernel_work(K, 10, 100.0, 2)
    # 10 tokens x 100 keys x 4 layers: scores and values 4*32*128 each key
    assert f == 10 * 100 * 4 * 4 * 32 * 128
    # K and V of 8 heads x 128 in bf16 per key per layer
    assert b == 10 * 100 * 4 * 2 * 8 * 128 * 2


def test_prefill_kernel_work_by_hand():
    f, b = reader("paged_prefill_roofline").kernel_work(K, 4, 2)
    assert f == 4 * 4 * 32 * 128 * (1 + 2 + 3 + 4)
    # per layer per token: K and V (2*8*128 bf16) and q in, out (2*32*128)
    assert b == 4 * 4 * (2 * 8 * 128 * 2 + 2 * 32 * 128 * 2)


def test_mfu_readers_by_hand():
    from bench import trace as T
    ms = 1e6                                   # ns
    dev = T.Device(ops=[], modules=[("jit_wrapped(1)", 0, 2 * ms),
                                    ("jit__decode_scan_paged(2)", 2 * ms,
                                     5 * ms)])
    ctx = {"trace": T.Trace({0: dev}, [], (0.0, 10 * ms)),
           "prefills": [3, 5], "decoded": [100], "model": K,
           "peaks": {"bf16_flops": 1e14}}
    prefill = flops.prefill(K, 3) + flops.prefill(K, 5)
    # the prefill step: prompts' FLOPs over the prefill programs' 2 ms
    assert reader("mfu.prefill").read(ctx) == 100 * prefill / (2e-3 * 1e14)
    # the whole step: prefills and decoded tokens over all 5 ms
    want = 100 * (prefill + flops.decode_token(K, 100)) / (5e-3 * 1e14)
    assert abs(reader("mfu.serve").read(ctx) - want) < 1e-9 * want
    assert reader("mfu.prefill").read(dict(ctx, peaks=None)) is None
