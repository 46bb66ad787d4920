# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# score_reduce.py — batched Eq. (1) scoring + masked argmin for the
# scheduler's candidate blocks (EcoSched engine="jax"): one Pallas row
# kernel, compiled on TPU, with interpret/ref modes on CPU chosen by
# REPRO_KERNELS (an unknown value raises).
