# The serve path's Pallas attention kernels: flash attention for prefill
# (flash_attention.py) and flash decoding (decode_attention.py), their jitted
# wrappers (ops.py) and plain-jnp references (ref.py). The POLCA tick step is
# not a kernel: it lives in provisioning/batched.py.
