"""Everything the benchmark takes from the program: the entry points a user
calls (README quickstart), built from a configuration file, with every flag
at its default. The weights are the benchmark's: dense leaves come from the
reference kind's ``init`` and are handed to the trainer, sparse rows from the
table's documented per-key rule under the run's seed."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import optax

from paddlebox_tpu import BoxWrapper
from paddlebox_tpu.data import SlotInfo, SlotSchema
from paddlebox_tpu.table import SparseOptimizerConfig
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig

DATE = "20260930"


def kind_modules(cfg: dict):
    """(program model builder, plain reference, work counter) of the kind."""
    k = cfg["kind"]
    return tuple(importlib.import_module(f"benchmark.{d}.{k}")
                 for d in ("models", "reference", "work"))


def make_weights(cfg: dict, seed: int):
    """Dense leaves from the seed, one jitted call, float32 on the device."""
    _, ref, _ = kind_modules(cfg)
    feat_width = 3 + cfg["embedx_dim"]
    return jax.jit(lambda k: ref.init(k, cfg, feat_width))(jax.random.PRNGKey(seed % (1 << 31)))


def make_dataset(cfg: dict, seed: int):
    so = cfg["sparse_opt"]
    box = BoxWrapper(
        embedx_dim=cfg["embedx_dim"],
        sparse_opt=SparseOptimizerConfig(
            embed_lr=so["embed_lr"], embedx_lr=so["embedx_lr"],
            initial_g2sum=so["initial_g2sum"], initial_range=so["initial_range"],
            embedx_threshold=so["embedx_threshold"], weight_bounds=so["weight_bounds"]),
        seed=seed,
    )
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(cfg["num_slots"])],
        label_slot="label",
    )
    ds = box.make_dataset(schema, batch_size=cfg["batch_size"])
    ds.set_date(DATE)
    return box, ds


def make_trainer(cfg: dict, box, weights):
    build, _, _ = kind_modules(cfg)
    model = build.build(cfg, box.layout.pull_width)
    step_cfg = TrainStepConfig(
        num_slots=cfg["num_slots"], batch_size=cfg["batch_size"], layout=box.layout,
        sparse_opt=box.sparse_opt, auc_buckets=cfg["auc_buckets"])
    ad = cfg["dense_opt"]
    trainer = CTRTrainer(
        model, step_cfg,
        dense_opt=optax.adam(ad["lr"], b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]))
    trainer.init_params(jax.random.PRNGKey(0))
    if jax.tree.structure(trainer.params) != jax.tree.structure(weights):
        raise ValueError("the reference's weights do not fit the program's model: "
                         f"{jax.tree.structure(weights)} vs {jax.tree.structure(trainer.params)}")
    for a, b in zip(jax.tree.leaves(trainer.params), jax.tree.leaves(weights)):
        if a.shape != b.shape:
            raise ValueError(f"weight shapes differ: {a.shape} vs {b.shape}")
    trainer.params = jax.tree.map(jnp.copy, weights)
    trainer.opt_state = trainer.dense_opt.init(trainer.params)
    return trainer
