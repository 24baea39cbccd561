"""End-to-end serving driver (the paper's kind is inference): serve a small
LM with batched requests through the continuous-batching `Scheduler`,
comparing fp vs packed sub-byte weights.

    PYTHONPATH=src python examples/serve_quantized.py
"""
import dataclasses
import time

import jax
import numpy as np

from repro.configs.qwen2p5_3b import smoke_config
from repro.models.api import build
from repro.nn.layers import QuantConfig, pack_dense_weights
from repro.serve.runtime import LMDecodeAdapter, Request, Scheduler


def fill_packed(qp, fp):
    if isinstance(qp, dict) and "w_packed" in qp:
        w = fp["w"]
        if w.ndim == 3:
            packed, scale = jax.vmap(lambda ww: pack_dense_weights(ww, 4))(w)
        else:
            packed, scale = pack_dense_weights(w, 4)
        return dict(qp, w_packed=packed, w_scale=scale)
    if isinstance(qp, dict):
        return {k: fill_packed(qp[k], fp[k]) if k in fp else qp[k]
                for k in qp}
    return qp


cfg_fp = smoke_config()
model_fp = build(cfg_fp)
params_fp = model_fp.init(jax.random.PRNGKey(0))

cfg_q = dataclasses.replace(
    cfg_fp, quant=QuantConfig(mode="int", w_bits=4, a_bits=8),
    kv_quant_bits=8)
model_q = build(cfg_q)
params_q = fill_packed(model_q.init(jax.random.PRNGKey(0)), params_fp)

reqs = [Request(prompt=np.array([2 + i, 40 + i, 7], np.int32),
                max_new_tokens=8) for i in range(4)]

for name, model, params in [("fp32", model_fp, params_fp),
                            ("w4a8+int8kv", model_q, params_q)]:
    sched = Scheduler(LMDecodeAdapter(model, params, max_len=32), 4)
    t0 = time.time()
    out = sched.serve([dataclasses.replace(r) for r in reqs])
    dt = time.time() - t0
    toks = sum(len(r.out) for r in out)
    print(f"[{name}] {toks} tokens in {dt:.2f}s; "
          f"sample: {out[0].out.tolist()}")

p_fp = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params_fp))
p_q = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params_q))
print(f"weight bytes: fp32 {p_fp}  packed-w4 {p_q}  ({p_fp / p_q:.1f}x)")
