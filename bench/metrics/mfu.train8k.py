"""Model FLOP/s utilization of training (percent), as ``mfu.train``
reads it: the operations the forward and backward passes need per token
(``counts/ssm.py``; recomputation not counted), times the untraced steps'
tokens per second, over chips times the device's bf16 peak."""
from bench.harness import reader

read = reader("mfu.train")
