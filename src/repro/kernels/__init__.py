"""Pallas TPU kernels (validated on CPU via interpret=True; Mosaic on TPU).

| kernel            | role                                             | oracle                |
|-------------------|--------------------------------------------------|-----------------------|
| pearson           | PAA prototype similarity (center+normalize+gram) | ref.pearson_ref       |
| cluster_agg       | PAA cluster-masked FedAvg (mix @ stacked params) | ref.cluster_agg_ref   |
| fingerprint       | per-client model commitment digests (chain)      | ref.fingerprint_ref   |
| flash_attention   | causal/SWA GQA attention, online softmax         | ref.attention_ref     |
| rwkv6_scan        | RWKV6 wkv recurrence, data-dependent decay       | ref.rwkv6_scan_ref    |
| selective_scan    | Mamba-1 selective scan, forward and backward     | mamba.chunked_scan    |
"""
from repro.kernels import ops, ref  # noqa: F401
