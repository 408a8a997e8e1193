# Pallas TPU kernels for the paper's compute hot-spots (HOG + SVM), each
# with a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py.
from repro.kernels.hog_gradient import hog_gradient
from repro.kernels.cell_hist import cell_hist
from repro.kernels.block_norm import block_norm
from repro.kernels.svm_matmul import svm_scores, score_matmul
from repro.kernels.fused_hog import fused_hog, dense_fused_hog
from repro.kernels.dense_grad_hist import dense_grad_hist
from repro.kernels.dense_block_norm import dense_block_norm
