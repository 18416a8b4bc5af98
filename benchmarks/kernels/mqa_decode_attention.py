"""ops/pallas/decode_attention.py `mqa_decode_attention`: one query a slot
over the live prefix of a cache whose one KV head every query head shares,
in the attention layers only.  Memory-bound: what it needs is the live keys
and values."""
from .. import model_jamba
from .ragged_decode_attention import live_positions


def work(ev, calls):
    cell, trace = ev["cell"], ev["trace"]
    jcfg = model_jamba.jamba_config(cell.config, cell.depth())
    layers = sum(jcfg.is_attention(i) for i in range(cell.depth()))
    d = jcfg.hidden_size // jcfg.num_attention_heads
    per_position = 2 * jcfg.num_key_value_heads * d * 2       # K and V, bf16
    pos = live_positions(ev["requests"], trace.t_start, trace.t_stop)
    nbytes = pos * per_position * layers
    # QK^T and PV: 4 x heads x D operations a position, 4 x kv heads x D bytes
    flops = nbytes * jcfg.num_attention_heads / jcfg.num_key_value_heads
    return {"mqa_decode_attention": (flops, nbytes)}
