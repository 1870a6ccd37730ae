"""CNN training/eval/profiling runner used by the compression pipeline.

Bundles a `CNNModel`, a synthetic dataset, and jitted train/eval steps. The
compression state `comp` ({layer_name: CompState}) is a *data* argument of
every jitted function — its structure is fixed at init (identity comps), so
codebook/mask edits made by the scheduler never trigger recompiles.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qat
from repro.core.layer_energy import LayerEnergyModel
from repro.core.stats import (
    LayerStats,
    collect_layer_stats,
    conv_weight_matrix,
    im2col,
)
from repro.data.synthetic import SyntheticImages
from repro.nn.cnn import CNNModel
from repro.nn.layers import QuantConfig
from repro.nn.spec import init_params
from repro.optim.optimizers import adamw, apply_updates


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


@dataclasses.dataclass
class CnnRunner:
    model: CNNModel
    dataset: SyntheticImages
    batch_size: int = 128
    lr: float = 1e-3
    qcfg: QuantConfig = QuantConfig.on()
    seed: int = 0
    use_kernel_stats: bool = False
    profile_mesh: Optional[object] = None  # 1-D tile mesh (sharding.tile_mesh)
    sweep_mesh: Optional[object] = None    # 1-D candidate mesh (sharding.sweep_mesh)

    def __post_init__(self):
        self.optimizer = adamw(self.lr)
        self._stats_cache: Optional[Dict[str, LayerStats]] = None
        model = self.model
        qcfg = self.qcfg

        def loss_fn(params, state, comp, batch):
            x, y = batch
            logits, new_state, _ = model.apply(
                params, state, x, train=True, qcfg=qcfg, comp=comp)
            return cross_entropy(logits, y), new_state

        def train_step(params, state, opt_state, comp, batch):
            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, state, comp, batch)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, new_state, opt_state, loss

        def eval_step(params, state, comp, batch):
            x, y = batch
            logits, _, _ = model.apply(
                params, state, x, train=False, qcfg=qcfg, comp=comp)
            return jnp.sum((jnp.argmax(logits, -1) == y).astype(jnp.int32))

        self._train_step = jax.jit(train_step)
        self._eval_step = jax.jit(eval_step)
        # candidate-sweep entry points (schedule ``search_mode="batched"``):
        # vmap over the leading candidate axis of the stacked trees, the data
        # batch shared across candidates. comp is a pure data argument with a
        # fixed tree structure, so one sweep compiles once per candidate
        # count and codebook/mask edits never retrigger compilation.
        self._train_step_raw = train_step
        self._eval_step_raw = eval_step
        self._cand_train_step = jax.jit(
            jax.vmap(train_step, in_axes=(0, 0, 0, 0, None)))
        self._cand_eval_step = jax.jit(
            jax.vmap(eval_step, in_axes=(0, 0, 0, None)))
        self._comp_eval_step = jax.jit(
            jax.vmap(eval_step, in_axes=(None, None, 0, None)))

        def gather_eval(params_s, state_s, comps_e, idx, batch):
            p = jax.tree.map(lambda x: x[idx], params_s)
            s = jax.tree.map(lambda x: x[idx], state_s)
            return jax.vmap(eval_step, in_axes=(0, 0, 0, None))(
                p, s, comps_e, batch)

        self._gather_eval_step = jax.jit(gather_eval)
        self._sweep_sharded = None
        self._tap_fn = jax.jit(
            lambda params, state, comp, x: model.apply(
                params, state, x, train=False, qcfg=qcfg, comp=comp,
                capture_taps=True)[2]
        )

    # ------------------------------------------------------------------ setup

    def init(self):
        key = jax.random.PRNGKey(self.seed)
        params = init_params(key, self.model.spec)
        state = init_params(key, self.model.state_spec)
        opt_state = self.optimizer.init(params)
        comp = self.identity_comp(params)
        return params, state, opt_state, comp

    def identity_comp(self, params) -> Dict[str, qat.CompState]:
        comp = {}
        for cl in self.model.comp_layers:
            w = self.model.get_weight(params, cl.name)
            comp[cl.name] = qat.identity_comp(w.shape, w.dtype)
        return comp

    # ------------------------------------------------------------------ train

    def train(self, params, state, opt_state, comp, n_steps: int,
              start_step: int = 0, log_every: int = 0):
        loss = jnp.nan
        for i in range(n_steps):
            batch = self.dataset.batch(start_step + i, self.batch_size, "train")
            params, state, opt_state, loss = self._train_step(
                params, state, opt_state, comp, batch)
            if log_every and (i + 1) % log_every == 0:
                print(f"  step {start_step + i + 1}: loss={float(loss):.4f}")
        return params, state, opt_state, float(loss)

    def accuracy(self, params, state, comp, n_batches: int = 8,
                 split: str = "val") -> float:
        correct = 0
        for i in range(n_batches):
            batch = self.dataset.batch(i, self.batch_size, split)
            correct += int(self._eval_step(params, state, comp, batch))
        return correct / (n_batches * self.batch_size)

    # ------------------------------------------------------- candidate sweep

    def _sweep_fns(self):
        """(train, eval, comp_eval) batched steps, honoring ``sweep_mesh``.

        Without a mesh these are the plain vmapped steps; with one, each is
        wrapped in `shard_map` over the 1-D candidate axis — every device
        trains/evaluates its local candidate slice, no collectives (the
        accept decision only needs the gathered per-candidate accuracies).
        """
        if self.sweep_mesh is None:
            return (self._cand_train_step, self._cand_eval_step,
                    self._comp_eval_step)
        if self._sweep_sharded is None:
            from jax.sharding import PartitionSpec
            from repro.distributed.sharding import SWEEP_AXIS

            mesh = self.sweep_mesh
            cand = PartitionSpec(SWEEP_AXIS)
            rep = PartitionSpec()
            vt = jax.vmap(self._train_step_raw, in_axes=(0, 0, 0, 0, None))
            ve = jax.vmap(self._eval_step_raw, in_axes=(0, 0, 0, None))
            vc = jax.vmap(self._eval_step_raw, in_axes=(None, None, 0, None))
            self._sweep_sharded = (
                jax.jit(jax.shard_map(
                    vt, mesh=mesh, in_specs=(cand, cand, cand, cand, rep),
                    out_specs=cand, check_vma=False)),
                jax.jit(jax.shard_map(
                    ve, mesh=mesh, in_specs=(cand, cand, cand, rep),
                    out_specs=cand, check_vma=False)),
                jax.jit(jax.shard_map(
                    vc, mesh=mesh, in_specs=(rep, rep, cand, rep),
                    out_specs=cand, check_vma=False)),
            )
        return self._sweep_sharded

    def _sweep_multiple(self) -> int:
        if self.sweep_mesh is None:
            return 1
        from repro.distributed.sharding import SWEEP_AXIS

        return int(self.sweep_mesh.shape[SWEEP_AXIS])

    @staticmethod
    def _n_candidates(comps) -> int:
        return int(jax.tree.leaves(comps)[0].shape[0])

    def train_batched(self, params, state, opt_state, comps, n_steps: int,
                      start_step: int = 0):
        """Train N stacked candidates in lockstep, one vmapped step per batch.

        ``params/state/opt_state/comps`` carry a leading candidate axis (see
        `qat.stack_pytrees` / `qat.broadcast_pytree`). Every candidate sees
        exactly the batch stream the serial path would feed it, so the
        per-candidate trajectories reproduce serial trial fine-tunes.
        Returns (params, state, opt_state, per-candidate final loss).
        """
        train_fn, _, _ = self._sweep_fns()
        n = self._n_candidates(comps)
        m = self._sweep_multiple()
        n_pad = -(-n // m) * m
        if n_pad != n:
            params, state, opt_state, comps = (
                qat.pad_leading(t, n_pad)
                for t in (params, state, opt_state, comps))
        loss = jnp.full((n_pad,), jnp.nan)
        for i in range(n_steps):
            batch = self.dataset.batch(start_step + i, self.batch_size,
                                       "train")
            params, state, opt_state, loss = train_fn(
                params, state, opt_state, comps, batch)
        if n_pad != n:
            params, state, opt_state = (
                jax.tree.map(lambda x: x[:n], t)
                for t in (params, state, opt_state))
            loss = loss[:n]
        return params, state, opt_state, np.asarray(jax.device_get(loss))

    def accuracy_batched(self, params, state, comps, n_batches: int = 8,
                         split: str = "val") -> np.ndarray:
        """Per-candidate accuracy vector: stacked params/state/comps."""
        _, eval_fn, _ = self._sweep_fns()
        n = self._n_candidates(comps)
        m = self._sweep_multiple()
        n_pad = -(-n // m) * m
        if n_pad != n:
            params, state, comps = (
                qat.pad_leading(t, n_pad) for t in (params, state, comps))
        correct = jnp.zeros((n_pad,), jnp.int32)
        for i in range(n_batches):
            batch = self.dataset.batch(i, self.batch_size, split)
            correct = correct + eval_fn(params, state, comps, batch)
        correct = np.asarray(jax.device_get(correct), np.float64)[:n]
        return correct / (n_batches * self.batch_size)

    def accuracy_comps(self, params, state, comps, n_batches: int = 8,
                       split: str = "val") -> np.ndarray:
        """Accuracy of N stacked comp variants sharing one params/state —
        one vmapped (or sharded) dispatch instead of one eval per variant.
        The schedule's lockstep elimination uses `accuracy_gather` (variants
        against *per-candidate* params); this is the shared-params form for
        ablations and sweeps over comp settings."""
        _, _, comp_fn = self._sweep_fns()
        n = self._n_candidates(comps)
        m = self._sweep_multiple()
        n_pad = -(-n // m) * m
        if n_pad != n:
            comps = qat.pad_leading(comps, n_pad)
        correct = jnp.zeros((n_pad,), jnp.int32)
        for i in range(n_batches):
            batch = self.dataset.batch(i, self.batch_size, split)
            correct = correct + comp_fn(params, state, comps, batch)
        correct = np.asarray(jax.device_get(correct), np.float64)[:n]
        return correct / (n_batches * self.batch_size)

    def accuracy_gather(self, params_s, state_s, comps_e, idx,
                        n_batches: int = 8, split: str = "val") -> np.ndarray:
        """Accuracy of E comp variants, element e using the params/state of
        stacked candidate ``idx[e]``.

        This serves `lockstep_backward_elimination`: one dispatch evaluates a
        whole elimination round's trial codebooks across ALL sweep candidates
        (each against its own fine-tuned weights). The candidate gather runs
        inside the jit, so E-element rounds cost one compiled call per
        distinct E (callers pad to fixed capacities). Always runs through
        the vmapped step — ``sweep_mesh`` shards the train/accept stages,
        but gathered per-request evals stay single-replica for now.
        """
        idx = jnp.asarray(idx, jnp.int32)
        n_e = self._n_candidates(comps_e)
        correct = jnp.zeros((n_e,), jnp.int32)
        for i in range(n_batches):
            batch = self.dataset.batch(i, self.batch_size, split)
            correct = correct + self._gather_eval_step(
                params_s, state_s, comps_e, idx, batch)
        correct = np.asarray(jax.device_get(correct), np.float64)
        return correct / (n_batches * self.batch_size)

    # ---------------------------------------------------------------- profile

    def capture_taps(self, params, state, comp, n_batches: int = 1):
        """Merged taps {layer: {a_int, w_int}} over a few val batches."""
        taps_all: Dict[str, dict] = {}
        for i in range(n_batches):
            x, _ = self.dataset.batch(i, self.batch_size, "val")
            taps = self._tap_fn(params, state, comp, x)
            for name, t in taps.items():
                if name in taps_all:
                    taps_all[name]["a_int"] = jnp.concatenate(
                        [taps_all[name]["a_int"], t["a_int"]], axis=0)
                else:
                    taps_all[name] = dict(t)
        return taps_all

    def layer_trace_inputs(self, cl, tap):
        """(W_mat (M,K) int, X_col (K,N) int) for one compressible layer."""
        if cl.kind == "conv":
            w_mat = conv_weight_matrix(tap["w_int"])
            x_col = im2col(tap["a_int"], (cl.kernel, cl.kernel), cl.stride,
                           cl.padding)
        else:
            w_mat = tap["w_int"].T  # dense w is (in, out) -> (M=out, K=in)
            a = tap["a_int"].reshape(-1, tap["a_int"].shape[-1])
            x_col = a.T
        return w_mat, x_col

    def profile(self, params, state, comp, *, n_batches: int = 1,
                max_tiles: int = 24) -> Dict[str, LayerStats]:
        """Per-layer systolic trace statistics from captured activations.

        Each layer's sampled tiles run as ONE batched kernel/oracle
        invocation (`repro.core.profiler`), sharded over `profile_mesh` when
        set. The result is cached on the runner so `energy_models` (and the
        schedule's ΔE refreshes) can reuse it without re-tracing.
        """
        taps = self.capture_taps(params, state, comp, n_batches)
        out: Dict[str, LayerStats] = {}
        for cl in self.model.comp_layers:
            w_mat, x_col = self.layer_trace_inputs(cl, taps[cl.name])
            # crc32, not hash(): str hash is salted per interpreter run,
            # which would resample tiles (and flip schedule decisions) on
            # every invocation of the same script
            out[cl.name] = collect_layer_stats(
                w_mat, x_col, max_tiles=max_tiles,
                key=jax.random.PRNGKey(
                    zlib.crc32(cl.name.encode()) % (2**31)),
                use_kernel=self.use_kernel_stats,
                mesh=self.profile_mesh,
            )
        self._stats_cache = out
        return out

    def layer_stats(self, params, state, comp,
                    **profile_kw) -> Dict[str, LayerStats]:
        """Cached per-layer stats; profiles (batched) on first use.

        Explicit ``profile_kw`` always re-profiles — a warm cache only
        answers the no-argument form (whatever settings produced it)."""
        if self._stats_cache is None or profile_kw:
            self.profile(params, state, comp, **profile_kw)
        return self._stats_cache

    def energy_models(self, params, comp,
                      stats: Optional[Dict[str, LayerStats]] = None,
                      batch: int = 1) -> Dict[str, LayerEnergyModel]:
        """LayerEnergyModel per compressible layer at inference batch size.

        ``stats=None`` falls back to the cache left by the latest `profile`
        call — trace statistics depend only weakly on fine-tuning, so ΔE
        refreshes reuse them instead of re-running the trace."""
        from repro.core.energy_lut import blended_lut
        from repro.core.layer_energy import weight_value_counts

        if stats is None:
            stats = self._stats_cache
            if stats is None:
                raise ValueError(
                    "no LayerStats given and no cached profile: call "
                    "runner.profile(...) first or pass stats explicitly")
        out = {}
        for cl in self.model.comp_layers:
            dims = cl.matmul_dims(batch)
            lut = blended_lut(stats[cl.name])
            w = self.model.get_weight(params, cl.name)
            w_int = qat.quantize_weight_int(w, comp[cl.name])
            if cl.kind == "conv":
                w_int = conv_weight_matrix(w_int)
            else:
                w_int = w_int.T
            counts = weight_value_counts(w_int, dims)
            out[cl.name] = LayerEnergyModel(cl.name, dims, lut, counts)
        return out

    def refresh_counts(self, params, comp,
                       models: Dict[str, LayerEnergyModel]) -> Dict[str, LayerEnergyModel]:
        """Recompute weight-value histograms after params/comp changed."""
        out = {}
        for cl in self.model.comp_layers:
            out[cl.name] = self.refresh_layer_counts(params, comp, models,
                                                     cl.name)
        return out

    def refresh_layer_counts(self, params, comp,
                             models: Dict[str, LayerEnergyModel],
                             layer: str) -> LayerEnergyModel:
        """One layer's refreshed histogram — the candidate sweep's per-trial
        ΔE refresh only needs the layer under search, so it skips the other
        layers' quantize dispatches."""
        from repro.core.layer_energy import weight_value_counts

        cl = self.model.comp_layer(layer)
        m = models[layer]
        w = self.model.get_weight(params, layer)
        w_int = qat.quantize_weight_int(w, comp[layer])
        w_int = conv_weight_matrix(w_int) if cl.kind == "conv" else w_int.T
        return m.with_counts(weight_value_counts(w_int, m.dims))


def total_energy(models: Dict[str, LayerEnergyModel]) -> float:
    return float(sum(m.energy for m in models.values()))
