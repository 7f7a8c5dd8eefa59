"""Causal flash attention, forward and backward, of one training step, on
ONE chip of the configuration's mesh.

Per sequence and query head, with S positions and head size D, a matrix
product over the full S x S square is 2*S*S*D operations and the causal half
of it S*S*D. The forward pass needs two (Q K^T, P V); the backward pass needs
four (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q). Recomputing Q K^T in
the backward kernels, and the forward pass again under rematerialisation, is
not counted. So 6*S*S*D per sequence, head and layer.

Bytes: Q, K, V, O and their four gradients read or written once in bfloat16
at the least; tiny beside the operations at S = 4096 (the kernel is bound by
the matrix unit).

A chip holds batch / data sequences and heads / model heads.
"""


def per_chip_step(cfg):
    t = cfg["trainer"]
    mesh = t.get("mesh") or {}
    seqs = t["batch"] // int(mesh.get("data", 1))
    heads = cfg["num_attention_heads"] // int(mesh.get("model", 1))
    kv_heads = max(1, cfg["num_key_value_heads"] // int(mesh.get("model", 1)))
    s, d, layers = t["seq"], cfg["head_dim"], cfg["num_hidden_layers"]
    flops = 6.0 * s * s * d * seqs * heads * layers
    # q, o, dq, do per query head; k, v, dk, dv per kv head; 2 bytes each
    elems = seqs * s * d * layers * (4 * heads + 4 * kv_heads)
    return {"flops": flops, "bytes": 2.0 * elems}
