"""Every token processed in the traced stretch, over the stretch and
the bf16 peak."""
from benchmark import work
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    prefill = _common.counter_delta(run, 'prefill_tokens', traced=True)
    decode = _common.counter_delta(run, 'decode_tokens', traced=True)
    if prefill is None or decode is None or prefill + decode <= 0:
        return None
    chunks = _common.traced_prefill_chunks(run)
    seen = sum(c for c, _ in chunks)
    # Contexts from the client's view, scaled to the engine's own count
    # of the tokens it processed in the stretch.
    pre_ctx = sum(c * off + c * (c + 1) / 2.0 for c, off in chunks)
    pre_ctx *= prefill / seen if seen else 0.0
    dec = _common.traced_decode_contexts(run)
    dec_ctx = float(sum(dec)) * (decode / len(dec) if dec else 0.0)
    requests = sum(1 for c, off in chunks if off == 0)
    flops = work.forward_flops(run['config'], prefill + decode,
                               pre_ctx + dec_ctx, decode + requests)
    return 100.0 * flops / (trace['window_s']
                            * trace['peak']['bf16_flops_per_s'])
