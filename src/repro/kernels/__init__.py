# Pallas TPU kernels for the paper's compute hot-spot: the counting hash
# table's block-level merge/query. Compiled on the TPU; the CPU test suite
# runs them in the Pallas interpreter (kernels/pallas.py decides).
