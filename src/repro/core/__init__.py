"""ScaleGNN core: communication-free sampling + 4D (DP x 3D-PMM) training."""
from repro.core.sampling import (
    SampleConfig, step_key, sample_uniform_exact, sample_stratified,
    extract_dense_block, extract_dense_block_stratified,
    extract_block_ell, extract_block_ell_stratified,
    stratified_col_scale, rescale_constants,
    MiniBatch, make_minibatch_exact, make_minibatch_stratified,
)
from repro.core.minibatch import (
    BlockFormat, GraphShards, Minibatch, MinibatchBuilder,
)
from repro.core.gcn_model import (
    GCNConfig, init_params, forward, sage_forward, cross_entropy_loss,
    accuracy, rmsnorm,
)
from repro.core.forward import ForwardEngine
from repro.core.fourd import (
    TrainOptions, FourDPlan, make_mesh_4d, build_plan, make_loss_fn,
    make_train_step, make_eval_step, param_specs, graph_data_specs,
)
from repro.core.pipeline import (
    PrefetchState, make_pipeline_fns, make_prefetched_train_step,
)
from repro.core import pmm3d, baselines, precision

__all__ = [
    "SampleConfig", "step_key", "sample_uniform_exact", "sample_stratified",
    "extract_dense_block", "extract_dense_block_stratified",
    "extract_block_ell", "extract_block_ell_stratified",
    "stratified_col_scale", "rescale_constants", "MiniBatch",
    "make_minibatch_exact", "make_minibatch_stratified",
    "BlockFormat", "GraphShards", "Minibatch", "MinibatchBuilder",
    "GCNConfig", "init_params", "forward", "sage_forward",
    "cross_entropy_loss", "accuracy", "rmsnorm",
    "ForwardEngine",
    "TrainOptions", "FourDPlan", "make_mesh_4d", "build_plan",
    "make_loss_fn", "make_train_step", "make_eval_step", "param_specs",
    "graph_data_specs",
    "PrefetchState", "make_pipeline_fns", "make_prefetched_train_step",
    "pmm3d", "baselines", "precision",
]
