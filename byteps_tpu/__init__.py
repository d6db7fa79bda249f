"""byteps_tpu — a TPU-native distributed training framework with the
capabilities of BytePS (reference: /root/reference, ruipeterpan/byteps).

Public API mirrors the reference's Horovod-compatible plugin surface
(reference: byteps/torch/__init__.py:23-28) re-designed for JAX/XLA:

    import byteps_tpu as bps
    bps.init()
    opt = bps.DistributedOptimizer(optax.adam(1e-3))
    step = bps.build_train_step(loss_fn, opt, bps.get_mesh())
"""

from .version import __version__

# Public names resolve lazily (PEP 562), each from the module that defines
# it.  `python -m byteps_tpu.server` imports this package first, and the
# server tier is a host process with no use for jax: resolved eagerly, the
# table below cost every server boot a full jax import, and put the TPU
# runtime one call away in a child whose parent holds the chip.
_LAZY = {
    ".common.api": (
        "init", "shutdown", "suspend", "resume",
        "rank", "size", "local_rank", "local_size",
        "leave", "get_membership", "on_membership_change",
        "get_ring", "drain_ps_server",
        "declare", "declared_key", "register_compressor", "get_ps_session",
        "push_pull", "push_pull_async", "push_pull_tree", "push_pull_sparse",
        "synchronize", "poll",
        "broadcast_parameters", "broadcast_optimizer_state",
        "get_pushpull_speed", "get_codec_stats", "get_fusion_stats",
        "get_transport_stats", "get_metrics", "get_server_stats",
        "get_health", "get_audit", "get_key_signals", "get_diagnosis",
        "get_tuner", "get_hierarchy", "get_autoscaler", "get_fleet",
        "get_device_profile", "get_step_scopes", "get_compile_log",
        "mark_step", "current_step"),
    ".parallel.async_ps": ("AsyncPSTrainer",),
    ".parallel.hierarchy": ("HierarchicalReducer", "SliceGroup"),
    ".parallel.server_opt": ("ServerOptTrainer",),
    ".parallel.embedding": ("EmbeddingTable",),
    ".ops.compression": ("Compression",),
    ".parallel.data_parallel": (
        "DistributedOptimizer", "DistributedGradientTransformation",
        "distributed_gradient_transform", "build_train_step"),
    ".parallel.mesh": (
        "make_mesh", "make_hierarchical_mesh", "make_slice_mesh",
        "get_mesh", "set_mesh", "reset_mesh"),
    ".parallel.cross_barrier": ("CrossBarrierDriver", "run_cross_barrier"),
    ".parallel.sharded": (
        "build_sharded_train_step", "shard_params", "init_sharded",
        "zero1_opt_specs", "zero1_init", "fsdp_param_specs", "fsdp_init"),
}
_HOME = {name: mod for mod, names in _LAZY.items() for name in names}
# Submodules exposed under a short name.
_HOME.update(collectives=".ops.collectives", compressor=".ops.compressor",
             ring_attention=".ops.ring_attention")


def __getattr__(name):
    import importlib
    home = _HOME.get(name)
    try:
        if home is None:
            # Any other submodule or subpackage (models, callbacks, utils,
            # common, parallel, ...): `bps.models` works without an import.
            value = importlib.import_module(f".{name}", __name__)
        else:
            mod = importlib.import_module(home, __name__)
            value = mod if home.endswith(f".{name}") else getattr(mod, name)
    except ModuleNotFoundError as e:
        if home is None and e.name == f"{__name__}.{name}":
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r}") from None
        raise
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))


__all__ = ["__version__", *_HOME, "models", "callbacks", "utils"]
