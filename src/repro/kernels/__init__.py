# Pallas TPU kernels for the paper's memory-bound hot spots:
#   favas_agg — favas_stream, the masked reweighted client aggregation
#               (Alg. 1 line 10 + eq. 3) that core/round_engine.py runs
#               before writing the new server row into the selected clients
#   luq       — LUQ logarithmic unbiased quantization (FAVAS[QNN], Remark 1)
#               and the cold-pool codec's encode/decode kernels
# ops.py = jit wrappers (kernel on TPU, interpret=True on CPU);
# ref.py = pure-jnp oracles; tests sweep shapes/dtypes with assert_allclose.
from repro.kernels.ops import favas_stream_flat, luq_quantize
