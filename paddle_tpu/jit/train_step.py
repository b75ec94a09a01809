"""Whole-train-step compilation: forward + loss + backward + optimizer
update traced into ONE XLA program.

This is the executor role of the reference's graph engines for the training
loop (reference: new executor paddle/fluid/framework/new_executor/, CUDA-graph
capture python/paddle/device/cuda/graphs.py) done the TPU-native way: trace
once, let XLA fuse the whole step, donate the parameter/optimizer buffers so
updates are in-place in HBM.

Eager ``loss.backward(); opt.step()`` dispatches hundreds of small device
programs per step; ``TrainStep`` turns the same user code (model, loss,
optimizer objects) into a single fused program — the difference is the
headline perf gap on TPU.

Usage::

    step = TrainStep(model, loss_fn, optimizer)      # loss_fn(out, *labels)
    loss = step(inputs, labels)                      # one fused XLA call
    ...
    step.sync()   # write updated arrays back into model/optimizer objects
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from .. import monitor
from ..framework.tape import no_grad
from ..framework.tensor import Tensor, wrap_array

# training-hot-path telemetry (ISSUE 5): elements of the first input
# leaf consumed per step — for (batch, seq) token-id inputs this IS the
# token count tools/train_bench.py quotes as train_tokens_total
_train_tokens = monitor.counter(
    "train_tokens_total", "elements of the first TrainStep input leaf "
    "consumed (== tokens for (batch, seq) token-id inputs)")


def _to_array(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def _keep(arr):
    """An array's NamedSharding, or None (single-device / no placement)."""
    from jax.sharding import NamedSharding
    sh = getattr(arr, "sharding", None)
    return sh if isinstance(sh, NamedSharding) else None


def _is_offloaded(sh):
    return sh is not None and \
        getattr(sh, "memory_kind", None) not in (None, "device")


def _pin(x, sh):
    """Constrain an in-program value to its initial placement; offloaded
    (host-memory) state returns home via a real transfer."""
    if x is None or sh is None:
        return x
    if _is_offloaded(sh):
        return jax.device_put(x, sh)
    return jax.lax.with_sharding_constraint(x, sh)


def _to_compute(x, sh):
    """Stream an offloaded operand into device memory for the update."""
    if x is None or not _is_offloaded(sh):
        return x
    return jax.device_put(x, sh.with_memory_kind("device"))


def _device_kind(sh):
    """The device-memory variant of a sharding (grads never offload —
    they are consumed immediately by the fused update)."""
    if _is_offloaded(sh):
        return sh.with_memory_kind("device")
    return sh


class TrainStep:
    """Compile model+loss+optimizer into a single donated-buffer XLA step.

    Parameters live as functional state inside the TrainStep between calls
    (the Tensor objects in ``model`` keep their stale pre-training values
    until ``sync()``); optimizer slot state is threaded the same way.
    ``amp_level``/``amp_dtype`` wrap the forward in ``amp.auto_cast``.
    """

    def __init__(self, model, loss_fn: Callable, optimizer,
                 amp_level: str = "O0", amp_dtype: str = "bfloat16",
                 accumulate_steps: int = 1, accumulate_avg: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype
        # gradient accumulation (reference: gradient_merge pass /
        # accumulate_steps): grads sum across k calls; the optimizer
        # update applies on every k-th call via lax.cond INSIDE the
        # compiled program — one executable, no per-branch recompiles
        self.accumulate_steps = int(accumulate_steps)
        # reference gradient_merge 'avg' knob: True -> mean of the k
        # micro-grads, False -> their sum
        self.accumulate_avg = bool(accumulate_avg)
        if self.accumulate_steps < 1:
            raise ValueError(
                f"accumulate_steps (gradient_merge k_steps) must be >= 1, "
                f"got {accumulate_steps}")

        all_params = list(model.parameters())
        self._train_params = [p for p in all_params
                              if getattr(p, "trainable", True)]
        self._frozen_params = [p for p in all_params
                               if not getattr(p, "trainable", True)]
        opt = optimizer
        opt._ensure_state(self._train_params)
        # params are copies, not references: the compiled step donates
        # these buffers, and donating the model's own arrays would leave it
        # holding deleted buffers until sync().  The optimizer state is
        # MOVED, not copied: slots + f32 masters are 12 B/param under
        # AdamW, and holding them twice is what stops a model sized to the
        # device from fitting; sync() hands them back.
        self._arrays = [jnp.copy(p._data) for p in self._train_params]
        self._states = {s: [opt._accumulators[s].pop(id(p))
                            for p in self._train_params]
                        for s in opt._state_slots}
        self._masters = [opt._master_weights.pop(id(p), None)
                         for p in self._train_params]
        self._update_fn = opt._functional_update_fn(self._train_params)
        # accumulate in fp32 whenever a master weight exists: summing k
        # bf16 micro-grads in bf16 rounds away exactly the small terms
        # the master-weight machinery protects.  Accumulators always live
        # in DEVICE memory (they're touched every micro-step) even when
        # the master they mirror is host-offloaded.
        def _accum_init(a, m):
            src = m if m is not None else a
            z = jnp.zeros_like(src)
            sh = _keep(src)
            if _is_offloaded(sh):
                z = jax.device_put(z, sh.with_memory_kind("device"))
            return z

        self._grad_accum = [
            _accum_init(a, m)
            for a, m in zip(self._arrays, self._masters)] \
            if self.accumulate_steps > 1 else []
        self._micro_step = 0
        self._compiled = None
        self._compiled_scan = None
        self._scan_fn = None
        self._last_loss = None

    # ------------------------------------------------------------------ build
    def _compute_placements(self):
        """Record every operand's home placement ONCE (params, optimizer
        state, masters, gradients) — shared by the single-step program
        and the K-step fused scan so their pinning cannot diverge."""
        param_shardings = [_keep(a) for a in self._arrays]
        state_shardings = {k: [_keep(a) for a in v]
                           for k, v in self._states.items()}
        master_shardings = [_keep(m) for m in self._masters]
        # ZeRO offload mode: on TPU the host-resident state stays
        # pinned_host ACROSS the program boundary (streamed in/out inside
        # the compiled step — overlappable transfers).  Other backends
        # (CPU tests) can't compile mixed-memory donated programs, so the
        # state is staged eagerly around the call instead — the same
        # semantics the reference's cpu_offload staging has
        # (group_sharded_stage3.py:85); host==device memory there anyway.
        offloaded = (any(_is_offloaded(s)
                         for v in state_shardings.values() for s in v)
                     or any(_is_offloaded(s) for s in master_shardings))
        self._offload_boundary = offloaded and \
            jax.default_backend() != "tpu"
        if self._offload_boundary:
            self._state_homes = (state_shardings, master_shardings)
            state_shardings = {k: [_device_kind(s) for s in v]
                               for k, v in state_shardings.items()}
            master_shardings = [_device_kind(s) for s in master_shardings]
        else:
            self._state_homes = None
        # grad placement follows the param's sharded state (or master) —
        # the gradient's consumer
        grad_shardings = []
        for i in range(len(self._arrays)):
            sh = next((state_shardings[k][i] for k in self._states
                       if state_shardings[k][i] is not None), None)
            grad_shardings.append(_device_kind(sh or master_shardings[i]))
        self._placements = (param_shardings, state_shardings,
                            master_shardings, grad_shardings)

    def _make_inner(self):
        """The pure single-micro-step function (forward + loss + backward
        + conditional optimizer apply).  ONE definition serves both the
        single-step jit and the body of the K-step ``lax.scan`` — the
        fused path cannot drift numerically from the escape hatch."""
        model = self.model
        loss_fn = self.loss_fn
        opt = self.optimizer
        train_params = self._train_params
        frozen_params = self._frozen_params
        update_fn = self._update_fn
        grad_clip = opt._grad_clip
        (param_shardings, state_shardings, master_shardings,
         grad_shardings) = self._placements

        if self.amp_level and self.amp_level != "O0":
            from .. import amp

            def cast_ctx():
                return amp.auto_cast(level=self.amp_level,
                                     dtype=self.amp_dtype)
        else:
            def cast_ctx():
                return contextlib.nullcontext()

        K = self.accumulate_steps

        def pure_step(arrays, states, masters, accum, frozen, lr, stepno,
                      apply_flag, in_leaves, label_leaves, treedefs):
            in_tree, label_tree = treedefs
            # ZeRO offload: stream host-resident optimizer state into
            # device memory for the fused update (returned home by _pin)
            states = {k: [_to_compute(a, s)
                          for a, s in zip(states[k], state_shardings[k])]
                      for k in states}
            masters = [_to_compute(m, s)
                       for m, s in zip(masters, master_shardings)]

            def loss_of(arrs):
                saved = [p._data for p in train_params]
                saved_frozen = [p._data for p in frozen_params]
                try:
                    for p, a in zip(train_params, arrs):
                        p._data = a
                    for p, a in zip(frozen_params, frozen):
                        p._data = a
                    inputs = jtu.tree_unflatten(
                        in_tree, [wrap_array(a) for a in in_leaves])
                    labels = jtu.tree_unflatten(
                        label_tree, [wrap_array(a) for a in label_leaves])
                    # the three scopes are names in the compiled
                    # program's metadata (a device trace splits the step
                    # by them); the arithmetic does not change
                    with no_grad(), cast_ctx(), \
                            jax.named_scope("train/model"):
                        outputs = model(*inputs)
                    outs = outputs if isinstance(outputs, (list, tuple)) \
                        else (outputs,)
                    with jax.named_scope("train/loss"):
                        loss = loss_fn(outputs, *labels)
                    out_arrays = [o._data for o in outs
                                  if isinstance(o, Tensor)]
                    return loss._data.astype(jnp.float32), out_arrays
                finally:
                    for p, s in zip(train_params, saved):
                        p._data = s
                    for p, s in zip(frozen_params, saved_frozen):
                        p._data = s

            (loss, outs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(arrays)
            # ZeRO stage-2/3 gradient placement: when a param's optimizer
            # state is sharded, land its gradient with the SAME sharding
            # (XLA lowers the grad psum to reduce-scatter — the pattern the
            # reference's stage-2 implements by hand,
            # group_sharded_optimizer_stage2.py:53).  Derived from the
            # state shardings so any shard_optimizer user gets it; a
            # group_sharded level of 'os' (stage-1) opts out — full grads
            # are that stage's definition.
            if getattr(opt, "_sharding_level", None) != "os":
                grads = [_pin(g, s) for g, s in zip(grads, grad_shardings)]

            def apply_clip(gs):
                if grad_clip is None:
                    return gs
                # real Parameter objects, not bare wraps: the clip consults
                # per-param flags (need_clip) that live on the Parameter
                pairs = [(p, wrap_array(g))
                         for p, g in zip(train_params, gs)]
                with no_grad():
                    clipped = grad_clip(pairs)
                return [g._data for _, g in clipped]

            with jax.named_scope("train/optimizer"):
                if K == 1:
                    grads = apply_clip(grads)
                    new_arrays, new_states, new_masters = update_fn(
                        lr, stepno, arrays, grads, states, masters)
                    new_accum = accum
                else:
                    # accumulate; the k-th call applies the averaged update
                    # and resets the accumulators — both arms of ONE
                    # compiled cond
                    summed = [a + g for a, g in zip(accum, grads)]

                    def do_update(operand):
                        arrays_, states_, masters_, summed_ = operand
                        # back to the grad dtype the update rule expects (the
                        # K=1 path feeds raw param-dtype grads)
                        denom = K if self.accumulate_avg else 1
                        avg = apply_clip([(g / denom).astype(a.dtype)
                                          for g, a in zip(summed_, arrays_)])
                        na, ns, nm = update_fn(lr, stepno, arrays_, avg,
                                               states_, masters_)
                        return na, ns, nm, [jnp.zeros_like(g) for g in summed_]

                    def skip_update(operand):
                        arrays_, states_, masters_, summed_ = operand
                        return arrays_, states_, masters_, summed_

                    new_arrays, new_states, new_masters, new_accum = \
                        jax.lax.cond(apply_flag, do_update, skip_update,
                                     (arrays, states, masters, summed))
            # pin outputs to their INITIAL placements: donated-buffer steps
            # otherwise drift to whatever GSPMD chose (e.g. ZeRO-1 params
            # silently becoming sharded after one step, erasing the
            # stage-1/2 vs stage-3 distinction and surprising eager readers)
            new_arrays = [_pin(a, s)
                          for a, s in zip(new_arrays, param_shardings)]
            new_states = {k: [_pin(a, s) for a, s in
                              zip(new_states[k], state_shardings[k])]
                          for k in new_states}
            new_masters = [_pin(a, s)
                           for a, s in zip(new_masters, master_shardings)]
            # accumulators follow the gradient placement (same reason as
            # the pins above: donated-buffer steps must not drift
            # shardings between calls, which would recompile every step)
            new_accum = [_pin(a, s)
                         for a, s in zip(new_accum, grad_shardings)]
            return (loss, outs, new_arrays, new_states, new_masters,
                    new_accum)

        return pure_step

    def _build(self):
        self._compute_placements()
        self._inner = self._make_inner()
        self._compiled = jax.jit(self._inner, donate_argnums=(0, 1, 2, 3),
                                 static_argnums=(10,))

    # ------------------------------------------------------------------- call
    def _prepare_args(self, inputs, labels):
        """Flatten user inputs/labels the way the compiled step expects —
        shared by __call__ and memory_analysis so their signatures cannot
        diverge."""
        if self._compiled is None:
            self._build()
        if not isinstance(inputs, (list, tuple)):
            inputs = (inputs,)
        if not isinstance(labels, (list, tuple)):
            labels = (labels,)
        in_leaves, in_tree = jtu.tree_flatten(
            inputs, is_leaf=lambda x: isinstance(x, Tensor))
        label_leaves, label_tree = jtu.tree_flatten(
            labels, is_leaf=lambda x: isinstance(x, Tensor))
        in_leaves = [_to_array(x) for x in in_leaves]
        label_leaves = [_to_array(x) for x in label_leaves]
        frozen = [p._data for p in self._frozen_params]
        return in_leaves, label_leaves, (in_tree, label_tree), frozen

    def _stage_in(self):
        """Boundary-mode offload: transfer host-resident state into device
        memory for the compiled call (no-op in program mode)."""
        if not getattr(self, "_offload_boundary", False):
            return self._states, self._masters
        homes_s, homes_m = self._state_homes
        states = {k: [jax.device_put(a, _device_kind(s))
                      if _is_offloaded(s) else a
                      for a, s in zip(self._states[k], homes_s[k])]
                  for k in self._states}
        masters = [jax.device_put(m, _device_kind(s))
                   if m is not None and _is_offloaded(s) else m
                   for m, s in zip(self._masters, homes_m)]
        return states, masters

    def _stage_out(self):
        """Boundary-mode offload: return the fresh state home to host
        memory after the compiled call."""
        if not getattr(self, "_offload_boundary", False):
            return
        homes_s, homes_m = self._state_homes
        self._states = {k: [jax.device_put(a, s)
                            if _is_offloaded(s) else a
                            for a, s in zip(self._states[k], homes_s[k])]
                        for k in self._states}
        self._masters = [jax.device_put(m, s)
                         if m is not None and _is_offloaded(s) else m
                         for m, s in zip(self._masters, homes_m)]

    def __call__(self, inputs, labels=()):
        """One fused train step.  ``inputs``/``labels`` are a Tensor/array or
        (possibly nested) tuple/list of them; returns the scalar loss Tensor
        (device value — no host sync unless you read it)."""
        in_leaves, label_leaves, treedefs, frozen = self._prepare_args(
            inputs, labels)

        opt = self.optimizer
        K = self.accumulate_steps
        self._micro_step += 1
        apply_now = (self._micro_step % K == 0)
        if apply_now:
            # the optimizer's schedule advances once per APPLIED update
            opt._global_step += 1
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        stepno = jnp.asarray(opt._global_step, jnp.int32)

        # signature only (no arrays pinned): lets program_text() lower the
        # compiled step later without holding batch data alive; shardings
        # ride along so the lowered text matches the executed partitioning
        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=_keep(a))

        self._last_sig = ([sds(a) for a in in_leaves],
                          [sds(a) for a in label_leaves], treedefs)
        states, masters = self._stage_in()
        (loss, outs, self._arrays, self._states, self._masters,
         self._grad_accum) = self._compiled(
            self._arrays, states, masters, self._grad_accum,
            frozen, lr, stepno, jnp.asarray(apply_now), in_leaves,
            label_leaves, treedefs)
        self._stage_out()
        if in_leaves:
            _train_tokens.inc(in_leaves[0].size)
        self._last_outputs = [wrap_array(o) for o in outs]
        self._last_loss = wrap_array(loss)
        return self._last_loss

    # ------------------------------------------------------- K-step fusion
    def _sched(self):
        """The optimizer's LRScheduler instance, or None for a plain
        float learning rate."""
        from ..optimizer.lr import LRScheduler
        lr = self.optimizer._learning_rate
        return lr if isinstance(lr, LRScheduler) else None

    def _sched_fingerprint(self):
        """Identity + hyperparameters of the current schedule, NESTED
        schedules included (LinearWarmup wraps another LRScheduler).
        The traced fn closes over the hyperparams as Python constants,
        so the cache (and the compiled scan) must be invalidated not
        just when the schedule OBJECT is swapped but also when it (or
        its inner schedule) is mutated in place — e.g. a checkpoint
        restore through ``Optimizer.set_state_dict`` rewriting
        ``base_lr``/``gamma`` on the same object.  ``last_epoch``/
        ``last_lr`` are excluded: they advance every step and are
        operands, not baked constants."""
        from ..optimizer.lr import LRScheduler

        def fp(sched):
            hyper = tuple(sorted(
                (k, repr(v)) for k, v in sched.state_dict().items()
                if k not in ("last_epoch", "last_lr")))
            nested = tuple(sorted(
                (k, fp(v)) for k, v in vars(sched).items()
                if isinstance(v, LRScheduler)))
            return (id(sched), hyper, nested)

        sched = self._sched()
        return None if sched is None else fp(sched)

    def _traced_sched_fn(self):
        """Memoized traced LR schedule (``step -> f32``), validated by
        abstract tracing; None when the schedule concretizes — the
        auto-detected signal to take the single-step escape hatch."""
        key = self._sched_fingerprint()
        cached = getattr(self, "_sched_fn_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        fn = None
        get = getattr(self.optimizer, "_traced_schedule", None)
        cand = get() if get is not None else None
        if cand is not None:
            try:
                jax.eval_shape(
                    lambda s: jnp.asarray(cand(s), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32))
                fn = cand
            except Exception:   # noqa: BLE001 — untraceable schedule
                fn = None
        self._sched_fn_cache = (key, fn)
        return fn

    @property
    def fused_supported(self) -> bool:
        """True when ``run_steps`` compiles ONE lax.scan dispatch for
        all k micro-steps (constant lr, or a schedule whose
        ``traced_lr`` validated); False means the schedule cannot be
        traced and run_steps falls back to k single-step dispatches."""
        if self._sched() is None:
            return True
        return self._traced_sched_fn() is not None

    def _build_scan(self):
        if self._compiled is None:
            self._build()
        inner = self._inner
        K = self.accumulate_steps
        sched_fn = self._traced_sched_fn()

        def scan_steps(arrays, states, masters, accum, frozen, micro0,
                       g0, sched0, lr_op, lr_factor, in_stacks,
                       label_stacks, treedefs):
            k = (in_stacks if in_stacks else label_stacks)[0].shape[0]

            def body(carry, xs):
                arrays, states, masters, accum = carry
                i, in_leaves, label_leaves = xs
                micro = micro0 + i + 1
                apply_flag = (micro % K) == 0
                # the schedule step counter advances once per MICRO
                # step (the hapi per-batch LRScheduler-callback
                # cadence); the optimizer step counter (adam bias
                # correction) once per APPLIED update
                stepno = (g0 + micro // K - micro0 // K).astype(jnp.int32)
                if sched_fn is None:
                    lr = lr_op
                else:
                    lr = jnp.asarray(sched_fn(sched0 + i),
                                     jnp.float32) * lr_factor
                loss, _outs, arrays, states, masters, accum = inner(
                    arrays, states, masters, accum, frozen, lr, stepno,
                    apply_flag, list(in_leaves), list(label_leaves),
                    treedefs)
                return (arrays, states, masters, accum), loss

            (arrays, states, masters, accum), losses = jax.lax.scan(
                body, (arrays, states, masters, accum),
                (jnp.arange(k, dtype=jnp.int32), tuple(in_stacks),
                 tuple(label_stacks)))
            return losses, arrays, states, masters, accum

        self._scan_fn = scan_steps
        # rebuild if the schedule is swapped OR mutated in place
        self._scan_sched = self._sched_fingerprint()
        self._compiled_scan = jax.jit(
            scan_steps, donate_argnums=(0, 1, 2, 3), static_argnums=(12,))

    def _fused_batch_stacks(self, batches):
        """Flatten every ``(inputs, labels)`` pair exactly the way
        ``__call__`` does and stack the leaves on a leading k axis —
        shared by run_steps and audit_fused so their signatures cannot
        diverge."""
        per_in, per_label = [], []
        treedefs = frozen = None
        for item in batches:
            if not (isinstance(item, (tuple, list)) and len(item) == 2):
                raise ValueError(
                    "run_steps takes a sequence of (inputs, labels) "
                    "pairs, each shaped as __call__ accepts")
            in_leaves, label_leaves, td, frozen = self._prepare_args(
                item[0], item[1])
            if treedefs is None:
                treedefs = td
            elif td != treedefs:
                raise ValueError(
                    "all run_steps batches must share one input/label "
                    "structure")
            per_in.append(in_leaves)
            per_label.append(label_leaves)
        in_stacks = [jnp.stack([s[j] for s in per_in])
                     for j in range(len(per_in[0]))]
        label_stacks = [jnp.stack([s[j] for s in per_label])
                        for j in range(len(per_label[0]))]
        return in_stacks, label_stacks, treedefs, frozen

    def _fused_scalars(self):
        """The traced bookkeeping scalars of one fused dispatch (all
        operands, never baked in — their change per call must not
        recompile)."""
        opt = self.optimizer
        sched = self._sched()
        return (jnp.asarray(self._micro_step, jnp.int32),
                jnp.asarray(opt._global_step, jnp.int32),
                jnp.asarray(0 if sched is None else sched.last_epoch,
                            jnp.int32),
                jnp.asarray(opt.get_lr(), jnp.float32),
                jnp.asarray(opt._lr_factor, jnp.float32))

    def run_steps(self, batches, k=None):
        """K micro-steps in ONE device dispatch: a ``lax.scan`` over the
        stacked batches, donation threaded through the scan carry, the
        learning rate and step number computed INSIDE the program from
        the traced schedule.  Semantically equivalent to::

            for inputs, labels in batches:
                loss_i = step(inputs, labels)
                schedule.step()          # if the lr is an LRScheduler

        (an LRScheduler advances once per micro-step — the cadence
        hapi's per-batch LRScheduler callback drives).  Returns the
        per-step losses as a device-resident ``(k,)`` Tensor; nothing
        syncs to the host unless the caller reads it.

        ``batches`` is a sequence of ``(inputs, labels)`` pairs, each as
        ``__call__`` accepts, all sharing one structure/shape/dtype.
        Escape hatch (auto-detected, ``fused_supported`` False): a
        schedule whose lr cannot be traced runs the same loop as k
        single-step dispatches.

        Schedule hyperparameter changes (object swap OR in-place
        mutation, nested schedules included) rebuild the fused program
        automatically.  The fused lr is computed functionally from the
        schedule's CURRENT hyperparams; after a partial in-place edit,
        refresh the host cache too (``sched.step(sched.last_epoch)``)
        or the single-step path will read the stale ``last_lr`` for one
        step — a full checkpoint restore carries a consistent
        ``last_lr`` and needs no refresh."""
        batches = list(batches)
        if k is None:
            k = len(batches)
        if k != len(batches) or k < 1:
            raise ValueError(
                f"k ({k}) must equal the number of batches "
                f"({len(batches)}) and be >= 1")
        sched = self._sched()
        if not self.fused_supported:
            losses = []
            for inputs, labels in batches:
                losses.append(self(inputs, labels)._data)
                if sched is not None:
                    sched.step()
            return wrap_array(jnp.stack(losses))
        if self._compiled_scan is None or \
                self._scan_sched != self._sched_fingerprint():
            self._build_scan()
        in_stacks, label_stacks, treedefs, frozen = \
            self._fused_batch_stacks(batches)
        scalars = self._fused_scalars()
        states, masters = self._stage_in()
        (losses, self._arrays, self._states, self._masters,
         self._grad_accum) = self._compiled_scan(
            self._arrays, states, masters, self._grad_accum, frozen,
            *scalars, in_stacks, label_stacks, treedefs)
        self._stage_out()
        if in_stacks:
            _train_tokens.inc(in_stacks[0].size)
        # host bookkeeping mirrors what the in-program schedule already
        # computed: micro/global step counters and the scheduler state
        K = self.accumulate_steps
        micro0 = self._micro_step
        self._micro_step += k
        self.optimizer._global_step += (micro0 + k) // K - micro0 // K
        if sched is not None:
            for _ in range(k):
                sched.step()
        self._last_outputs = []
        self._last_loss = wrap_array(losses[k - 1])
        return wrap_array(losses)

    def fused_program_spec(self, batches):
        """The fused K-step program's EXACT traced function + abstract
        operand list — the shared tracing spec under :meth:`audit_fused`
        (hazard rules) and ``analysis.cost``'s FLOPs/HBM estimator
        (ISSUE 10: the train-lane MFU numerator), so both see the one
        call contract ``run_steps`` executes.  Returns ``(fn, args,
        donate_argnums, static_argnums)``; params/optimizer state ride
        as abstract avals — no device work, nothing materialized."""
        if not self.fused_supported:
            raise ValueError(
                "the LR schedule is not traceable — run_steps uses the "
                "single-step escape hatch and there is no fused program "
                "to audit")
        if self._compiled_scan is None or \
                self._scan_sched != self._sched_fingerprint():
            self._build_scan()
        # abstract stacking: only the FIRST batch's leaf shapes/dtypes
        # are read and a leading k axis prepended — no jnp.stack, no
        # device allocation for the k real batches
        batches = list(batches)
        k = len(batches)
        first = batches[0]
        if not (isinstance(first, (tuple, list)) and len(first) == 2):
            raise ValueError(
                "fused_program_spec takes the same (inputs, labels) "
                "pairs as run_steps")
        in_leaves, label_leaves, treedefs, _frozen = self._prepare_args(
            first[0], first[1])
        in_stacks = [jax.ShapeDtypeStruct((k,) + tuple(a.shape), a.dtype)
                     for a in in_leaves]
        label_stacks = [jax.ShapeDtypeStruct((k,) + tuple(a.shape),
                                             a.dtype)
                        for a in label_leaves]

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=_keep(a))

        def staged_sds(a):
            if a is None:
                return None
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=_device_kind(_keep(a)))

        arrays = [staged_sds(a) for a in self._arrays]
        states = {s: [staged_sds(a) for a in v]
                  for s, v in self._states.items()}
        masters = [staged_sds(m) for m in self._masters]
        accum = [staged_sds(a) for a in self._grad_accum]
        frozen = [sds(p._data) for p in self._frozen_params]
        scalars = tuple(sds(x) for x in self._fused_scalars())
        args = (arrays, states, masters, accum, frozen, *scalars,
                in_stacks, label_stacks, treedefs)
        return self._scan_fn, args, (0, 1, 2, 3), (12,)

    def audit_fused(self, batches, **limits):
        """``analysis.audit_callable`` on the fused K-step program:
        traces the EXACT operand list and donation contract run_steps
        executes (:meth:`fused_program_spec`) and returns the
        ProgramAudit.  The certification lane tools/train_bench.py
        gates on: no host callbacks, donation intact, no f32 creep.

        When the step's operands carry NamedShardings over a >1 mesh
        (DataParallel / sharded optimizer state), the tier-3 SPMD
        audit (``analysis.spmd``) runs automatically: gradient-sync
        collectives are named and priced (the HLO tier sees the
        GSPMD-inserted all-reduces no jaxpr walk can), its hazard
        findings merge into this audit, and the full distributed audit
        rides on ``audit.spmd``."""
        from ..analysis import audit_callable
        fn, args, donate, static = self.fused_program_spec(batches)
        audit = audit_callable(
            fn, *args, donate_argnums=donate, static_argnums=static,
            name="TrainStep.run_steps", **limits)
        try:
            import math as _math
            from ..analysis.spmd import (audit_spmd_fused,
                                         mesh_axes_of_args)
            axes = mesh_axes_of_args(jtu.tree_leaves(tuple(
                a for i, a in enumerate(args) if i not in static)))
            if _math.prod(axes.values() or [1]) > 1:
                audit.spmd = audit_spmd_fused(
                    self, batches, publish=limits.get("publish", True))
                audit.findings.extend(audit.spmd.findings)
        except Exception:   # noqa: BLE001 — tier 3 must never fail tier 1
            pass
        return audit

    def static_peak_hbm(self, inputs, labels=()) -> float:
        """Static peak-HBM estimate of the single-step program
        (``analysis.spmd.estimate_peak_hbm``: a buffer-lifetime walk
        honoring the step's donation contract) — the memory-gate
        pre-verdict ``bench.py`` quotes next to the measured
        ``planned_peak_bytes``, available from a trace alone: no
        compile, no device execution, so a gate-rejecting config costs
        milliseconds instead of a failed run."""
        import jax.numpy as jnp
        from ..analysis.spmd import estimate_peak_hbm
        in_leaves, label_leaves, treedefs, frozen = self._prepare_args(
            inputs, labels)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        stepno = jnp.asarray(self.optimizer._global_step + 1, jnp.int32)
        closed = jax.make_jaxpr(self._inner, static_argnums=(10,))(
            self._arrays, self._states, self._masters, self._grad_accum,
            frozen, lr, stepno, jnp.asarray(True), in_leaves,
            label_leaves, treedefs)
        donated = [a for tree in (self._arrays, self._states,
                                  self._masters, self._grad_accum)
                   for a in jtu.tree_leaves(tree)]
        return estimate_peak_hbm(closed, donated_avals=donated)

    # -------------------------------------------------------------- analysis
    def _lower(self, in_leaves, label_leaves, treedefs, as_avals=False):
        """Single lowering call site shared by memory_analysis and
        program_text, so the argument list cannot drift from the compiled
        signature.  ``as_avals=True`` lowers the params/state operands as
        ShapeDtypeStructs carrying the staged shardings — no arrays are
        materialized (in boundary-mode offload, _stage_in would otherwise
        device_put the whole host-resident state just to lower)."""
        frozen = [p._data for p in self._frozen_params]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        stepno = jnp.asarray(self.optimizer._global_step + 1, jnp.int32)
        if as_avals:
            def staged_sds(a):
                if a is None:
                    return None
                return jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=_device_kind(_keep(a)))

            arrays = [staged_sds(a) for a in self._arrays]
            states = {k: [staged_sds(a) for a in v]
                      for k, v in self._states.items()}
            masters = [staged_sds(m) for m in self._masters]
            accum = [staged_sds(a) for a in self._grad_accum]
        else:
            arrays = self._arrays
            states, masters = self._stage_in()
            accum = self._grad_accum
        return self._compiled.lower(
            arrays, states, masters, accum, frozen, lr, stepno,
            jnp.asarray(True), in_leaves, label_leaves, treedefs)

    def memory_analysis(self, inputs, labels=(), return_hlo=False):
        """Per-device compiled memory profile of the whole train step
        (argument/output/temp/alias bytes) — the observability the
        reference's sharding stages expose through max_memory_allocated.
        ZeRO stage differences are visible here: stage-3 shrinks the donated
        parameter arguments, stage-2 shrinks gradient temps.

        Memoized per input-shape signature: repeat calls (periodic
        monitoring) don't pay a whole-step recompile."""
        in_leaves, label_leaves, treedefs, frozen = self._prepare_args(
            inputs, labels)
        key = (tuple((a.shape, str(a.dtype))
                     for a in in_leaves + label_leaves),
               treedefs, bool(return_hlo))
        cached = getattr(self, "_mem_cache", {}).get(key)
        if cached is not None:
            return dict(cached)
        lowered = self._lower(in_leaves, label_leaves, treedefs)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        try:   # XLA's analytic FLOP count for the WHOLE step program —
               # the numerator of MFU (BASELINE config 5)
            flops = float((compiled.cost_analysis() or {}).get("flops", 0.0))
        except Exception:   # noqa: BLE001 — backend without cost model
            flops = 0.0
        out = {
            "flops_per_step": flops,
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
            # ZeRO offload moves bytes from the device columns above into
            # these host columns (populated on backends with distinct
            # host/device memories, i.e. TPU)
            "host_argument_bytes": getattr(
                mem, "host_argument_size_in_bytes", 0),
            "host_output_bytes": getattr(
                mem, "host_output_size_in_bytes", 0),
            "host_temp_bytes": getattr(mem, "host_temp_size_in_bytes", 0),
        }
        if return_hlo:
            out["hlo"] = lowered.as_text()
        if not hasattr(self, "_mem_cache"):
            self._mem_cache = {}
        self._mem_cache[key] = dict(out)
        return out

    def program_text(self) -> Optional[str]:
        """The whole-step program as StableHLO text (the TPU-native analog
        of the reference's partitioned dist_main_program) — available
        after the first call; shardings appear as sdy.sharding (Shardy)
        attributes.  Lowered from avals only (no state materialized) and
        memoized per signature."""
        sig = getattr(self, "_last_sig", None)
        if self._compiled is None or sig is None:
            return None
        in_sds, label_sds, treedefs = sig
        key = (tuple((s.shape, str(s.dtype)) for s in in_sds + label_sds),
               treedefs)
        cache = getattr(self, "_program_text_cache", None)
        if cache is not None and cache[0] == key:
            return cache[1]
        text = self._lower(in_sds, label_sds, treedefs,
                           as_avals=True).as_text()
        self._program_text_cache = (key, text)
        return text

    def compiled_text(self) -> Optional[str]:
        """The whole-step program as the backend compiled it (optimised
        HLO text) — available after the first call.  Each instruction's
        ``metadata={op_name=...}`` carries the ``jax.named_scope`` path
        it came from (``train/model/...``, ``train/loss``,
        ``train/optimizer``), which a device trace's bare instruction
        names do not: this text maps one to the other.  Lowered from
        avals like ``program_text``; the compile is the step's own, so
        with a persistent compilation cache it is read back, not
        redone."""
        sig = getattr(self, "_last_sig", None)
        if self._compiled is None or sig is None:
            return None
        in_sds, label_sds, treedefs = sig
        return self._lower(in_sds, label_sds, treedefs,
                           as_avals=True).compile().as_text()

    # ------------------------------------------------------------------- sync
    def sync(self):
        """Write the functional state back into the model Parameters and the
        optimizer's accumulators (call before checkpointing/eval)."""
        opt = self.optimizer
        for p, a in zip(self._train_params, self._arrays):
            p._data = a
        for s in opt._state_slots:
            for p, arr in zip(self._train_params, self._states[s]):
                opt._accumulators[s][id(p)] = arr
        for p, m in zip(self._train_params, self._masters):
            if m is not None:
                opt._master_weights[id(p)] = m

    @property
    def last_outputs(self) -> List[Tensor]:
        return getattr(self, "_last_outputs", [])
