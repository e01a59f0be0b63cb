"""GradientDescent — the fused autodiff trainer.

TPU-native replacement for the reference's per-layer backward units
(znicz gd*.py with hand-derived CUDA/OpenCL gradient kernels; surface per
manualrst_veles_algorithms.rst items 5, 8, 9, 11, 13).  One unit owns the
whole training step:

    loss = evaluator.loss(forward_chain(params, x), target)
    grads = jax.grad(loss)          # replaces every hand-written kernel
    params = solver.update(...)     # sgd/momentum/adagrad/adadelta/adam

— all traced into ONE jitted XLA program with parameters and solver state
donated (in-place HBM update).  Validation/test minibatches flow through
the same program: ``lax.cond`` on the minibatch class skips the update
while still returning loss/n_err, so there is exactly one compiled
executable for the whole train/eval cycle.

Per-layer hyper-parameter overrides (extras item 13) resolve at trace
time from each forward unit's attributes; the learning-rate schedule
(lr_adjust) is traced on the global step; when the workflow runs under a
device mesh the gradient ``psum`` over the ``dp`` axis happens inside
this same program (see veles_tpu.parallel).
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.loader.base import TRAIN
from veles_tpu.memory import Array
from veles_tpu.models.all2all import All2AllSoftmax
from veles_tpu.models.dropout import DropoutForward
from veles_tpu.models.evaluator import EvaluatorMSE
from veles_tpu.models.lr_adjust import get_schedule
from veles_tpu.models.solvers import get_solver
from veles_tpu import prng as prng_mod
from veles_tpu.telemetry.spans import annotation


class GradientDescent(AcceleratedUnit):
    """The trainer unit (replaces a whole chain of znicz GD units)."""

    VIEW_GROUP = "TRAINER"
    FUSABLE = False  # self-jits with donation; owns its own dispatch

    def __init__(self, workflow, forwards=None, evaluator=None, loader=None,
                 solver="sgd", learning_rate=0.01, learning_rate_bias=None,
                 weights_decay=0.0, weights_decay_bias=None, l1_vs_l2=0.0,
                 gradient_moment=0.0, gradient_moment_bias=None,
                 lr_schedule="constant", lr_schedule_params=None,
                 prng_key="trainer", mesh=None, augment=None,
                 pp_microbatches=None, **kwargs):
        super(GradientDescent, self).__init__(workflow, **kwargs)
        #: jax.sharding.Mesh — when set, the fused step is sharded over
        #: it (dp batch split + psum, tp weight split; see
        #: veles_tpu.parallel.sharding).  Replaces the reference's entire
        #: ZeroMQ master-slave gradient exchange (SURVEY.md §2.3).
        self.mesh = mesh
        self.forwards = list(forwards) if forwards else []
        self.evaluator = evaluator
        self.loader = loader
        self.solver_name = solver
        self.learning_rate = learning_rate
        self.learning_rate_bias = learning_rate_bias \
            if learning_rate_bias is not None else learning_rate
        self.weights_decay = weights_decay
        self.weights_decay_bias = weights_decay_bias \
            if weights_decay_bias is not None else weights_decay
        self.l1_vs_l2 = l1_vs_l2
        self.gradient_moment = gradient_moment
        self.gradient_moment_bias = gradient_moment_bias \
            if gradient_moment_bias is not None else gradient_moment
        self.lr_schedule = lr_schedule
        self.lr_schedule_params = lr_schedule_params or {}
        #: in-graph train-time augmentation traced into the fused step
        #: (ops/augment.py); eval sees clean data.  A dict spec like
        #: {"kind": "image", "pad": 4} survives snapshots (a raw
        #: callable works too but won't pickle)
        self.augment = augment
        #: microbatches per pipeline step on a ``pp`` mesh (None →
        #: the pp extent; larger shrinks the bubble fraction
        #: (S-1)/(M+S-1) at the cost of smaller per-stage matmuls)
        self.pp_microbatches = pp_microbatches
        self.prng = prng_mod.get(prng_key)
        self.lr_multiplier = 1.0  # Rollback adjusts this

        self.global_step = 0
        self.opt_state = {}      # {layer_idx: {param: {slot: Array}}}
        self.loss = Array()
        self.n_err = Array()
        #: device-side per-class epoch accumulator [class, (n_err,
        #: loss_sum, samples)] — DecisionGD reads it once per epoch
        #: instead of syncing on every minibatch
        self.epoch_acc = Array()
        self.demand("forwards", "evaluator", "loader")

    def __getstate__(self):
        state = super(GradientDescent, self).__getstate__()
        if state.get("mesh") is not None \
                and not isinstance(state["mesh"], dict):
            # a jax Mesh holds Device objects — unpicklable.  Persist
            # the concrete AXIS SPEC; initialize() rebuilds the mesh
            # over the resuming process's devices (which must supply a
            # matching chip count — to re-shard onto a different
            # topology, override .mesh before initialize).  A not-yet-
            # initialized restore re-pickles the spec dict as-is.
            state["mesh"] = {"__mesh_axes__": dict(state["mesh"].shape)}
        return state

    def init_unpickled(self):
        super(GradientDescent, self).init_unpickled()
        self._train_step_ = None
        self._span_step_ = None
        self._shardings_ = None
        self._pp_plan_ = None
        #: master-side epoch accumulator in float64: the master's device
        #: program never runs, and f32 accumulation of worker sample
        #: counts stops being exact past ~2^24 samples/epoch — the
        #: epoch-completion threshold would never fire (a hang).
        #: Volatile: resume abandons in-flight accounting, like
        #: pending_minibatches_ (ref: base.py:205).
        self._master_acc_ = numpy.zeros((3, 3), numpy.float64)

    # -- hyper-parameter resolution (extras item 13) ---------------------------

    def _layer_hp(self, unit, param_name):
        hp = unit.hyperparams()

        def pick(specific, generic, default):
            v = hp.get(specific)
            if v is None:
                v = hp.get(generic)
            return default if v is None else v

        if param_name == "bias":
            return {
                "lr": pick("learning_rate_bias", "learning_rate",
                           self.learning_rate_bias),
                "decay": pick("weights_decay_bias", "weights_decay",
                              self.weights_decay_bias),
                "moment": pick("gradient_moment_bias", "gradient_moment",
                               self.gradient_moment_bias),
                "l1_vs_l2": self.l1_vs_l2,
            }
        return {
            "lr": pick("learning_rate", None, self.learning_rate),
            "decay": pick("weights_decay", None, self.weights_decay),
            "moment": pick("gradient_moment", None, self.gradient_moment),
            "l1_vs_l2": self.l1_vs_l2,
        }

    # -- lifecycle -------------------------------------------------------------

    def initialize(self, device=None, **kwargs):
        from veles_tpu.units import MissingDemand
        if isinstance(self.mesh, dict):
            # an axis-spec dict — a snapshot restore (__getstate__'s
            # sentinel form) or a user override like {'dp': 4} — is
            # materialized here: over ALL processes' devices for a
            # multi-host gang, over the target device's backend
            # otherwise (build_mesh raises a clear error on a
            # mismatched chip count)
            import jax
            axes = self.mesh.get("__mesh_axes__", self.mesh)
            if jax.process_count() > 1:
                # a gang spans every process's chips — but still on
                # the target device's PLATFORM (a numpy-backend run on
                # a GPU-default host must not grab GPU devices)
                from veles_tpu.parallel import build_mesh
                self.mesh = build_mesh(dict(axes), devices=jax.devices(
                    device.jax_device.platform) if device is not None
                    else None)
            elif device is not None:
                self.mesh = device.make_mesh(axes)
            else:
                from veles_tpu.parallel import build_mesh
                self.mesh = build_mesh(dict(axes))
        if not self.forwards or self.evaluator is None \
                or self.loader is None:
            raise MissingDemand(self, {"forwards", "evaluator", "loader"})
        for u in self.forwards:
            if not u.is_initialized:
                raise MissingDemand(self, {"forwards[%s]" % u.name})
        if isinstance(self.evaluator, EvaluatorMSE) \
                and getattr(self.loader, "minibatch_targets", None) is None:
            raise MissingDemand(self, {"loader.minibatch_targets"})
        if self.mesh is not None and self.mesh.shape.get("pp", 1) > 1:
            self._pp_plan_ = self._make_pp_plan()
        if self.mesh is not None and self._pp_plan_ is None:
            # hand each forward the mesh for what GSPMD cannot derive
            # from the single-program form (models/attention.mha_apply):
            # sequence parallelism is a COMMUNICATION SCHEDULE (the
            # ppermute ring), and a Mosaic kernel is opaque to the
            # partitioner, so it runs per shard.  Not under pp: the
            # GPipe trunk already runs inside its own shard_map.
            # Volatile (trailing _) — re-established here on every
            # snapshot resume.
            for u in self.forwards:
                u.sp_mesh_ = self.mesh
        solver = get_solver(self.solver_name)
        if not self.opt_state:  # fresh (not restored from snapshot)
            for i, u in enumerate(self.forwards):
                per_param = {}
                for name, arr in u.param_arrays().items():
                    # init on device from the already-uploaded param —
                    # no host round-trip (solver slots are zeros_like;
                    # pulling them to host and re-uploading costs 2×
                    # model size over the host↔HBM link)
                    slots = solver.init(arr.devmem)
                    per_param[name] = {}
                    for s, v in slots.items():
                        a = Array()
                        a.devmem = v
                        per_param[name][s] = a
                self.opt_state[i] = per_param
        self.loss.reset(numpy.zeros((), numpy.float32))
        self.n_err.reset(numpy.zeros((), numpy.int32))
        self.epoch_acc.reset(numpy.zeros((3, 3), numpy.float32))
        # span serving: the loader hands whole class spans to this unit,
        # which scans over them in one dispatch (kills per-minibatch
        # Python/dispatch overhead — the reference paid it per kernel).
        # Auto-enable only (None); a builder's explicit False stands.
        if getattr(self.loader, "supports_span", False) \
                and self.loader.span_serving is None:
            self.loader.span_serving = True
        super(GradientDescent, self).initialize(device=device, **kwargs)
        for layer in self.opt_state.values():
            for slots in layer.values():
                for arr in slots.values():
                    arr.initialize(self.device)

    # -- pipeline parallelism (pp first-class at the trainer, r5) --------------

    def _make_pp_plan(self):
        """Locate the pipelineable TRUNK — the longest contiguous run
        of shape-preserving forwards with identical type/config/param
        shapes (e.g. the TransformerBlock × N stack) — and split it
        into ``pp`` stages.  SURVEY §2.3: every strategy a first-class
        mesh-axis config; pp mirrors sp's r4 treatment (an explicit
        communication schedule the trainer owns, param storage stays
        replicated like sp/dp)."""
        S = self.mesh.shape["pp"]
        for ax in ("tp", "fsdp", "sp", "ep"):
            if self.mesh.shape.get(ax, 1) > 1:
                raise ValueError(
                    "pp composes with dp only (got %s>1): shard the "
                    "trunk over pp×dp, or drop the pp axis" % ax)

        def signature(u):
            return (type(u).__name__, repr(sorted(
                u.export_config().items(), key=str)),
                tuple(sorted((n, a.mem.shape)
                             for n, a in u.param_arrays().items())))

        best = (0, 0)
        i = 0
        units = self.forwards
        while i < len(units):
            u = units[i]
            if isinstance(u, DropoutForward) \
                    or tuple(u.input.shape) != tuple(u.output.shape):
                i += 1
                continue
            j = i
            sig = signature(u)
            while j < len(units) and not isinstance(
                    units[j], DropoutForward) \
                    and tuple(units[j].input.shape) == tuple(
                        units[j].output.shape) \
                    and signature(units[j]) == sig:
                j += 1
            if j - i > best[1] - best[0]:
                best = (i, j)
            i = j
        start, end = best
        n = end - start
        if n < S or n % S:
            raise ValueError(
                "pp=%d needs a homogeneous shape-preserving trunk with "
                "a stage-divisible length; found %d matching units "
                "(forwards[%d:%d]) — use a layer count divisible by pp"
                % (S, n, start, end))
        n_micro = int(self.pp_microbatches or S)
        mb = self.loader.max_minibatch_size
        dp_total = self.mesh.shape.get("dp", 1)  # fsdp rejected above
        per_dev = mb // dp_total
        if mb % dp_total or per_dev % n_micro:
            raise ValueError(
                "minibatch %d must divide into dp extent %d and then "
                "into %d pp microbatches per dp slice"
                % (mb, dp_total, n_micro))
        batch_axes = ("dp",) if dp_total > 1 else ()
        return {"start": start, "end": end, "stages": S,
                "n_micro": n_micro, "batch_axes": batch_axes}

    def _pp_trunk_apply(self, params, h):
        """Stack the trunk units' params stage-major and run the GPipe
        schedule (parallel/pipeline.gpipe_train) inside the fused
        step — fwd, bwd (transposed ppermute schedule) and the solver
        update share one XLA program."""
        from veles_tpu.parallel.pipeline import gpipe_train
        plan = self._pp_plan_
        start, end, S = plan["start"], plan["end"], plan["stages"]
        trunk = self.forwards[start:end]
        k = len(trunk) // S
        stacked = {
            j: {name: jnp.stack(
                [params[start + s * k + j][name] for s in range(S)])
                for name in params[start]}
            for j in range(k)}
        unit0 = trunk[0]

        def stage_fn(stage_params, h):
            for j in range(k):
                p = stage_params[j]
                if getattr(unit0, "remat", False):
                    h = jax.checkpoint(unit0.apply)(p, h)
                else:
                    h = unit0.apply(p, h)
            return h

        return gpipe_train(self.mesh, stage_fn, stacked, h,
                           plan["n_micro"],
                           batch_axes=plan["batch_axes"])

    # -- the fused program -----------------------------------------------------

    def _forward(self, params, x, key, train):
        """Compose the chain; returns the trainer-facing head output
        (logits for a softmax head).  On a ``pp`` mesh the trunk runs
        the GPipe schedule; pre/post units run replicated."""
        h = x
        plan = self._pp_plan_
        i = 0
        while i < len(self.forwards):
            if plan is not None and i == plan["start"]:
                h = self._pp_trunk_apply(params, h)
                i = plan["end"]
                continue
            u = self.forwards[i]
            p = {name: params[i][name] for name in params[i]}
            if isinstance(u, DropoutForward):
                if train:
                    key, sub = jax.random.split(key)
                    h = u.apply_train(p, h, sub)
                else:
                    h = u.apply(p, h)
            elif isinstance(u, All2AllSoftmax) and i == len(
                    self.forwards) - 1:
                h = u.logits(p, h)
            elif getattr(u, "remat", False):
                # recompute this unit in the backward pass instead of
                # saving its internals (nn_units.ForwardBase.remat)
                h = jax.checkpoint(u.apply)(p, h)
            else:
                h = u.apply(p, h)
            i += 1
        return h

    def _target_of(self, labels, targets):
        return targets if isinstance(self.evaluator, EvaluatorMSE) \
            else labels

    def _make_minibatch_step(self):
        """The per-minibatch fused body shared by the single-step jit and
        the span scan: forward + loss + (cond) backward/solver + epoch
        accounting.

        Health (telemetry/health.py): the step also returns a 5-vector
        ``[grad_norm, weight_norm, update_ratio, nonfinite, loss]``
        computed IN-GRAPH (cheap jnp reductions over pytrees XLA fuses
        into the step) — the host reads one tiny array instead of
        re-walking the parameters.  Under the ``skip_step`` policy a
        non-finite update is dropped in the same program: parameters
        and solver state keep their pre-step values, and the
        epoch-accounting row contributes only its sample count (the
        epoch-completion gate still advances), so a single poisoned
        minibatch cannot contaminate the weights before the host even
        hears about it.  The policy knobs are baked at trace time;
        the dispatch sites rebuild the cached steps when they change
        (:meth:`_maybe_invalidate_steps`)."""
        from veles_tpu.telemetry.health import health_config
        hcfg = health_config()
        health_on = hcfg["enabled"]
        skip_nonfinite = health_on and hcfg["policy"] == "skip_step"
        solver = get_solver(self.solver_name)
        schedule = get_schedule(self.lr_schedule, **self.lr_schedule_params)
        hps = {i: {name: self._layer_hp(u, name)
                   for name in u.param_arrays()}
               for i, u in enumerate(self.forwards)}
        is_mse = isinstance(self.evaluator, EvaluatorMSE)

        augment_fn = None
        if self.augment is not None:
            if callable(self.augment):
                augment_fn = self.augment
            else:
                from veles_tpu.ops.augment import make_augment
                augment_fn = make_augment(**dict(self.augment))

        target_is_input = getattr(self.evaluator, "TARGET_IS_INPUT",
                                  False)

        def loss_and_metrics(params, x, target, size, key, train):
            if train and augment_fn is not None:
                key, sub = jax.random.split(key)
                x = augment_fn(x, sub)
            if target_is_input:
                # sequence objectives (EvaluatorNextToken) score the
                # model against its own input tokens
                target = x
            y = self._forward(params, x, key, train)
            loss = self.evaluator.loss(y, target, size)
            if hasattr(self.evaluator, "train_metrics"):
                n_err = self.evaluator.train_metrics(y, target, size)
            elif is_mse:
                n_err = jnp.zeros((), jnp.int32)
            else:
                # argmax over logits is valid for any softmax-CE head,
                # explicit All2AllSoftmax or plain logits layer alike
                pred = jnp.argmax(y, axis=-1).astype(jnp.int32)
                mask = jnp.arange(y.shape[0]) < size
                n_err = jnp.sum(jnp.where(
                    mask, (pred != target).astype(jnp.int32), 0))
            return loss, n_err

        def sq_norm(tree):
            leaves = jax.tree_util.tree_leaves(tree)
            total = jnp.zeros((), jnp.float32)
            for leaf in leaves:
                total = total + jnp.sum(
                    jnp.square(leaf.astype(jnp.float32)))
            return total

        def train_step(params, opt_state, acc, x, target, size, class_id,
                       step_no, lr_mult, key):
            def do_train(args):
                params, opt_state = args
                (loss, n_err), grads = jax.value_and_grad(
                    loss_and_metrics, has_aux=True)(
                        params, x, target, size, key, True)
                # lr_mult is traced so Rollback's lr changes don't
                # recompile the whole program
                scale = lr_mult * schedule(step_no)
                new_params, new_opt = {}, {}
                for i in params:
                    new_params[i], new_opt[i] = {}, {}
                    for name in params[i]:
                        hp = dict(hps[i][name])
                        hp["lr"] = hp["lr"] * scale
                        p, s = solver.update(
                            params[i][name], grads[i][name],
                            opt_state[i][name], hp)
                        new_params[i][name] = p
                        new_opt[i][name] = s
                if not health_on:
                    return (new_params, new_opt, loss, n_err,
                            jnp.zeros((5,), jnp.float32))
                grad_sq = sq_norm(grads)
                bad = jnp.where(
                    jnp.isfinite(loss) & jnp.isfinite(grad_sq),
                    jnp.float32(0), jnp.float32(1))
                if skip_nonfinite:
                    keep_old = bad > 0
                    new_params = jax.tree.map(
                        lambda new, old: jnp.where(keep_old, old, new),
                        new_params, params)
                    new_opt = jax.tree.map(
                        lambda new, old: jnp.where(keep_old, old, new),
                        new_opt, opt_state)
                weight_sq = sq_norm(new_params)
                update_sq = sq_norm(jax.tree.map(
                    lambda new, old: new.astype(jnp.float32)
                    - old.astype(jnp.float32), new_params, params))
                health = jnp.stack([
                    jnp.sqrt(grad_sq), jnp.sqrt(weight_sq),
                    jnp.sqrt(update_sq)
                    / (jnp.sqrt(weight_sq) + jnp.float32(1e-12)),
                    bad, loss.astype(jnp.float32)])
                return new_params, new_opt, loss, n_err, health

            def do_eval(args):
                params, opt_state = args
                loss, n_err = loss_and_metrics(
                    params, x, target, size, key, False)
                bad = jnp.where(jnp.isfinite(loss), jnp.float32(0),
                                jnp.float32(1)) if health_on \
                    else jnp.float32(0)
                health = jnp.stack([
                    jnp.float32(0), jnp.float32(0), jnp.float32(0),
                    bad, loss.astype(jnp.float32)])
                return params, opt_state, loss, n_err, health

            params, opt_state, loss, n_err, health = jax.lax.cond(
                class_id == TRAIN, do_train, do_eval,
                (params, opt_state))
            # per-class epoch accounting stays on device: one row of
            # [n_err, loss*size, size] added to the class's
            # accumulator.  The size row stays in SAMPLE units — the
            # DCN master's epoch-completion gate compares it against
            # class_lengths.  Sequence objectives (EvaluatorNextToken)
            # count errors per TOKEN, so their n_err scales down by
            # tokens-per-sample: the decision layer's error %% is then
            # the wrong-token percentage, and loss (already per token)
            # divided by samples stays the per-token CE.
            per_sample = 1
            if hasattr(self.evaluator, "metric_units"):
                per_sample = self.evaluator.metric_units(x)
            row = jnp.stack([n_err.astype(jnp.float32) / per_sample,
                             loss * size, size.astype(jnp.float32)])
            if skip_nonfinite:
                # a skipped TRAIN step keeps its NaN loss/err out of
                # the epoch accumulator but must still contribute its
                # SIZE: the DCN master closes epochs when acc[cls][2]
                # reaches the class lengths (decision.py), so zeroing
                # the sample count would hang the distributed epoch.
                # Eval steps are never skipped — their row stays
                # intact regardless of loss finiteness (under
                # warn/halt the poison stays visible on purpose).
                skipped = (health[3] > 0) & (class_id == TRAIN)
                row = jnp.where(
                    skipped,
                    jnp.stack([jnp.float32(0), jnp.float32(0),
                               size.astype(jnp.float32)]),
                    row)
            onehot = (jnp.arange(3) == class_id).astype(jnp.float32)
            acc = acc + onehot[:, None] * row[None, :]
            return params, opt_state, acc, loss, n_err, health

        return train_step

    def _build_train_step(self):
        from veles_tpu.telemetry import track_jit
        train_step = self._make_minibatch_step()
        if self.mesh is None:
            return track_jit(
                "trainer.minibatch_step",
                jax.jit(train_step, donate_argnums=(0, 1, 2)))
        params_sh, opt_sh, x_sh, tgt_sh, rep = self._ensure_shardings()
        return track_jit("trainer.minibatch_step", jax.jit(
            train_step,
            in_shardings=(params_sh, opt_sh, rep, x_sh, tgt_sh,
                          rep, rep, rep, rep, rep),
            out_shardings=(params_sh, opt_sh, rep, rep, rep, rep),
            donate_argnums=(0, 1, 2)))

    def _build_span_step(self):
        """One jitted dispatch per class span: ``lax.scan`` over the
        loader's index schedule, gathering each minibatch from the
        HBM-resident dataset in-graph (north star: the whole accelerated
        segment is one XLA program per run)."""
        minibatch_step = self._make_minibatch_step()

        def span_step(params, opt_state, acc, ds, tgt_ds, idx, sizes,
                      class_id, step0, lr_mult, base_key):
            def body(carry, xs):
                params, opt_state, acc, k = carry
                idx_k, size_k = xs
                x = jnp.take(ds, idx_k, axis=0, mode="clip")
                tgt = jnp.take(tgt_ds, idx_k, axis=0, mode="clip")
                key = jax.random.fold_in(base_key, k)
                (params, opt_state, acc, loss, n_err,
                 health) = minibatch_step(
                    params, opt_state, acc, x, tgt, size_k, class_id,
                    step0 + k.astype(jnp.float32), lr_mult, key)
                return (params, opt_state, acc, k + 1), (loss, n_err,
                                                         health)

            (params, opt_state, acc, _), (losses, n_errs,
                                          healths) = jax.lax.scan(
                body, (params, opt_state, acc, jnp.int32(0)), (idx, sizes))
            # health over the span: last step's norms/loss, nonfinite
            # steps SUMMED so a single poisoned minibatch mid-span is
            # still counted at the boundary read
            health = jnp.concatenate([
                healths[-1, :3], jnp.sum(healths[:, 3])[None],
                healths[-1, 4:]])
            return params, opt_state, acc, losses[-1], n_errs[-1], health

        from veles_tpu.telemetry import track_jit
        if self.mesh is None:
            return track_jit(
                "trainer.span_step",
                jax.jit(span_step, donate_argnums=(0, 1, 2)))
        from jax.sharding import NamedSharding, PartitionSpec as P
        params_sh, opt_sh, x_sh, tgt_sh, rep = self._ensure_shardings()
        batch_axes = x_sh.spec[0] if len(x_sh.spec) else None
        idx_sh = NamedSharding(self.mesh, P(None, batch_axes))
        self._idx_sharding_ = idx_sh  # _run_span pre-places host indices
        sizes_sh = rep
        return track_jit("trainer.span_step", jax.jit(
            span_step,
            in_shardings=(params_sh, opt_sh, rep, rep, rep, idx_sh,
                          sizes_sh, rep, rep, rep, rep),
            out_shardings=(params_sh, opt_sh, rep, rep, rep, rep),
            donate_argnums=(0, 1, 2)))

    def _ensure_shardings(self):
        """NamedShardings over self.mesh — XLA then inserts the gradient
        psum over dp and the tp collectives on ICI."""
        if self._shardings_ is not None:
            return self._shardings_
        from veles_tpu.parallel import sharding as shlib
        mesh = self.mesh
        params_sh = {
            i: {name: shlib.param_sharding(mesh, name, arr.mem.shape)
                for name, arr in u.param_arrays().items()}
            for i, u in enumerate(self.forwards)}
        opt_sh = {
            i: {name: {s: params_sh[i][name]
                       for s in self.opt_state[i][name]}
                for name in self.opt_state[i]}
            for i in self.opt_state}
        # Adam's step counter is a scalar — replicate it
        for i, layer in self.opt_state.items():
            for name, slots in layer.items():
                for s, arr in slots.items():
                    if len(arr.shape) == 0:  # dev-born slots have no mem
                        opt_sh[i][name][s] = shlib.replicated(mesh)
        mb = self.loader.max_minibatch_size
        x_shape = self.loader.minibatch_data.shape
        # dim 1 of the DATA minibatch is a sequence dim ONLY when the
        # FIRST forward consumes it as one (SEQ_DIM1_INPUT on the unit
        # class — attention/transformer/embedding/recurrent); image
        # workflows' dim 1 is height and must not sp-shard, even if a
        # sequence unit appears later in the chain (ADVICE.md r4 #2)
        has_seq = bool(self.forwards) and getattr(
            self.forwards[0], "SEQ_DIM1_INPUT", False)
        x_sh = shlib.batch_sharding(
            mesh, len(x_shape), dim0=mb,
            seq_dim1=x_shape[1]
            if has_seq and len(x_shape) >= 2 else None)
        tgt_ndim = len(self.loader.minibatch_targets.shape) \
            if isinstance(self.evaluator, EvaluatorMSE) \
            else len(self.loader.minibatch_labels.shape)
        tgt_sh = shlib.batch_sharding(mesh, tgt_ndim, dim0=mb)
        rep = shlib.replicated(mesh)
        self._shardings_ = (params_sh, opt_sh, x_sh, tgt_sh, rep)
        return self._shardings_

    # -- execution -------------------------------------------------------------

    def _gather_state(self):
        # the step DONATES params/opt_state (donate_argnums=(0, 1)) —
        # donatable_devmem detaches buffers whose host mirror shares
        # the allocation (XLA:CPU zero-copy device_put / map_read
        # views), the span-step heap-corruption fix (ROUND6_NOTES.md)
        params = {i: {name: arr.donatable_devmem()
                      for name, arr in u.param_arrays().items()}
                  for i, u in enumerate(self.forwards)}
        opt_state = {i: {name: {s: arr.donatable_devmem()
                                for s, arr in slots.items()}
                         for name, slots in layer.items()}
                     for i, layer in self.opt_state.items()}
        return params, opt_state

    def _adopt_state(self, new_params, new_opt):
        for i, u in enumerate(self.forwards):
            for name, arr in u.param_arrays().items():
                arr.devmem = new_params[i][name]
        for i, layer in self.opt_state.items():
            for name, slots in layer.items():
                for s, arr in slots.items():
                    arr.devmem = new_opt[i][name][s]

    def _mesh_prepare(self, params, opt_state):
        """Re-distribute state pytrees onto the mesh when a host-side
        write (rollback, snapshot resume) reset a leaf to single-device
        placement — one leaf check suffices since all leaves travel
        together; normally state adopts the sharded step outputs."""
        from veles_tpu.parallel import sharding as shlib
        params_sh, opt_sh, _, _, rep = self._shardings_
        if self.epoch_acc.devmem.sharding != rep:
            self.epoch_acc.devmem = shlib.put(self.epoch_acc.devmem, rep)
        i0 = next(iter(params))
        n0 = next(iter(params[i0]))
        if params[i0][n0].sharding != params_sh[i0][n0]:
            params = jax.tree.map(shlib.put, params, params_sh)
            opt_state = jax.tree.map(shlib.put, opt_state, opt_sh)
        return params, opt_state

    def _maybe_invalidate_steps(self):
        """health.py promises config is read per call, but the
        in-graph skip guard is baked into the step at trace time —
        rebuild the cached jitted steps when the effective
        (enabled, skip_step) pair changes so tests and ``-c``
        overrides of ``root.common.health.*`` keep applying after
        the first dispatch (one recompile, not silence)."""
        from veles_tpu.telemetry.health import health_config
        hcfg = health_config()
        sig = (hcfg["enabled"],
               hcfg["enabled"] and hcfg["policy"] == "skip_step")
        if getattr(self, "_health_sig_", sig) != sig:
            self._train_step_ = None
            self._span_step_ = None
        self._health_sig_ = sig

    def run(self):
        l = self.loader
        if getattr(l, "span_fresh_", False):
            self._run_span()
            return
        self._maybe_invalidate_steps()
        if self._train_step_ is None:
            self._train_step_ = self._build_train_step()
        with annotation("veles.gd.dispatch"):
            params, opt_state = self._gather_state()
            # under the asynchronous input pipeline these devmem reads are
            # already-on-device batch handles installed at pop time
            # (loader/prefetch.py) — no synchronous host→HBM upload here
            x = l.minibatch_data.devmem
            labels = l.minibatch_labels.devmem
            targets = getattr(l, "minibatch_targets", None)
            is_mse = isinstance(self.evaluator, EvaluatorMSE)
            target = targets.devmem if is_mse else labels
            if self._shardings_ is not None:
                from veles_tpu.parallel import sharding as shlib
                _, _, x_sh, tgt_sh, _ = self._shardings_
                pf = getattr(l, "prefetch_", None)
                if pf not in (None, False) \
                        and not shlib.is_cross_process(x_sh):
                    # teach the uploader thread the step's input shardings
                    # so the put below becomes a no-op re-place
                    pf.set_placement(
                        x_sh,
                        labels_sharding=None if is_mse else tgt_sh,
                        targets_sharding=tgt_sh if is_mse else None)
                if shlib.is_cross_process(x_sh):
                    # feed the host mirror directly: putting the local device
                    # buffer would download it again just to re-assemble
                    x = l.minibatch_data.map_read().mem
                    target = (l.minibatch_targets if isinstance(
                        self.evaluator, EvaluatorMSE)
                        else l.minibatch_labels).map_read().mem
                x = shlib.put(x, x_sh)
                target = shlib.put(target, tgt_sh)
                params, opt_state = self._mesh_prepare(params, opt_state)
            key = self.prng.peek_key(self.global_step)
            new_params, new_opt, acc, loss, n_err, health = \
                self._train_step_(
                    params, opt_state, self.epoch_acc.donatable_devmem(),
                    x, target,
                    jnp.int32(l.minibatch_size),
                    jnp.int32(l.minibatch_class),
                    jnp.float32(self.global_step),
                    jnp.float32(self.lr_multiplier), key)
        self.epoch_acc.devmem = acc
        self._adopt_state(new_params, new_opt)
        self.loss.devmem = loss
        self.n_err.devmem = n_err
        if l.minibatch_class == TRAIN:
            self.global_step += 1
            self._observe_health(health)

    def _run_span(self):
        """Consume a whole class span in ONE dispatch (lax.scan inside
        jit over the loader's index schedule)."""
        l = self.loader
        l.span_fresh_ = False
        self._maybe_invalidate_steps()
        if self._span_step_ is None:
            self._span_step_ = self._build_span_step()
        with annotation("veles.gd.dispatch"):
            params, opt_state = self._gather_state()
            is_mse = isinstance(self.evaluator, EvaluatorMSE)
            ds = l.dataset_dev
            tgt = l.targets_dev if is_mse else l.labels_dev
            if self._shardings_ is not None or self.mesh is not None:
                _, _, _, _, rep = self._ensure_shardings()
                if ds.sharding != rep:
                    # re-home the loader's dataset onto the mesh (replicated,
                    # like each reference slave holding a full copy) — the
                    # single-device original is released, not duplicated
                    l.rehome_dataset(rep)
                    ds = l.dataset_dev
                    tgt = l.targets_dev if is_mse else l.labels_dev
                params, opt_state = self._mesh_prepare(params, opt_state)
            idx = l.span_indices_
            if getattr(self, "_idx_sharding_", None) is not None:
                # multi-process meshes reject numpy args with non-trivial
                # shardings — assemble the global index array explicitly
                from veles_tpu.parallel import sharding as shlib
                idx = shlib.put(idx, self._idx_sharding_)
            key = self.prng.peek_key(self.global_step)
            new_params, new_opt, acc, loss, n_err, health = \
                self._span_step_(
                    params, opt_state, self.epoch_acc.donatable_devmem(),
                    ds, tgt,
                    idx, l.span_sizes_,
                    jnp.int32(l.span_class_), jnp.float32(self.global_step),
                    jnp.float32(self.lr_multiplier), key)
        self.epoch_acc.devmem = acc
        self._adopt_state(new_params, new_opt)
        self.loss.devmem = loss
        self.n_err.devmem = n_err
        if l.span_class_ == TRAIN:
            self.global_step += len(l.span_sizes_)
            self._observe_health(health, force=True)

    def _observe_health(self, health, force=False):
        """Feed the jitted step's health vector to the process-wide
        monitor — ONE small device→host read per observed dispatch,
        decimated by ``root.common.health.sync_every`` on the
        per-minibatch path (a span boundary always syncs: it is
        already a host touchpoint).  Acts on the policy verdict: halt
        stops the workflow gracefully instead of crashing."""
        from veles_tpu.telemetry import health as health_lib
        cfg = health_lib.health_config()
        if not cfg["enabled"]:
            return
        self._health_ticks_ = getattr(self, "_health_ticks_", 0) + 1
        every = max(int(cfg["sync_every"]), 1)
        if not force and self._health_ticks_ % every:
            return
        with annotation("veles.gd.health_sync"):
            vals = numpy.asarray(health)
        action = health_lib.monitor.on_train_step(
            grad_norm=float(vals[0]), weight_norm=float(vals[1]),
            update_ratio=float(vals[2]), nonfinite=float(vals[3]),
            loss=float(vals[4]), unit=self.name)
        if action == "halt":
            self.error(
                "health policy 'halt': non-finite training step - "
                "stopping the workflow (process stays up; see "
                "GET /healthz and the flight recorder)")
            if self._workflow is not None:
                self._workflow.on_workflow_finished()

    # -- elastic DCN sync (parameter-server semantics over the
    #    coordinator, ref: the Znicz GD units' weight-delta exchange the
    #    reference routed through workflow.py:478-558) ---------------------------

    negotiates_on_connect = True

    def _read_params_numpy(self):
        out = {}
        for i, u in enumerate(self.forwards):
            out[i] = {}
            for name, arr in u.param_arrays().items():
                arr.map_read()
                out[i][name] = numpy.array(arr.mem)
        return out

    def generate_data_for_slave(self, slave=None):
        """Master → worker: the job carries the current parameters."""
        return {"params": self._read_params_numpy()}

    def apply_data_from_master(self, data):
        """Worker: install the master's parameters and remember them as
        the delta baseline for this job."""
        params = data["params"]
        for i, u in enumerate(self.forwards):
            for name, arr in u.param_arrays().items():
                arr.map_invalidate()
                arr.mem[...] = params[i][name]
                arr.unmap()
        self._job_params_ = params

    def generate_data_for_master(self):
        """Worker → master: parameter deltas (async-SGD update) + the
        epoch accounting accumulated on this worker since the last send."""
        now = self._read_params_numpy()
        base = getattr(self, "_job_params_", None) or now
        delta = {i: {name: now[i][name] - base[i][name]
                     for name in now[i]} for i in now}
        acc = self.read_epoch_acc(reset_classes=(0, 1, 2), as_array=True)
        return {"delta": delta, "acc": acc}

    def apply_data_from_slave(self, data, slave=None):
        """Master: merge the worker's delta into the live parameters and
        fold its epoch accounting into the (float64) master
        accumulator."""
        for i, u in enumerate(self.forwards):
            for name, arr in u.param_arrays().items():
                arr.map_write()
                arr.mem[...] += data["delta"][i][name]
                arr.unmap()
        self._master_acc_ += numpy.asarray(data["acc"], numpy.float64)

    def drop_slave(self, slave=None):
        pass  # in-flight deltas from a dead worker are simply lost

    def read_epoch_acc(self, reset_classes=(), as_array=False):
        """One host sync: {class: (n_err, loss_sum, samples)}; resets the
        requested class rows for the next epoch."""
        if self.is_master:
            # the master's graph never runs; its accounting lives in the
            # float64 host accumulator fed by apply_data_from_slave
            acc = numpy.array(self._master_acc_)
            for c in reset_classes:
                self._master_acc_[c] = 0
        else:
            self.epoch_acc.map_read()
            acc = numpy.array(self.epoch_acc.mem)
            if len(reset_classes):
                self.epoch_acc.map_write()
                for c in reset_classes:
                    self.epoch_acc.mem[c] = 0
                self.epoch_acc.unmap()
        if as_array:
            return acc
        return {c: (float(acc[c, 0]), float(acc[c, 1]), float(acc[c, 2]))
                for c in range(3)}

    def step(self, **tensors):
        raise RuntimeError("GradientDescent dispatches its own program")
