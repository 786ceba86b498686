"""The trainer under test: ALBERT masked-LM peers that train together through
`hivemind_tpu.optim.Optimizer` (and, where a peer is a slice, `SliceOptimizer` on a
mesh of the cell's chips), built the way `chip_smoke.py`'s phases T and M build
them. One process, one thread per peer, each with its own DHT node.

Set-up: weights on the device from the seed in one jitted call; the step compiled;
the correctness check against the plain reference; `warm_epochs` epochs closed, so
that every program of a step and of a round exists. Then the window: the peers step
for `seconds`; a step counts when `Optimizer.step` returned inside it. Swarm rounds
are inside the window."""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List

from perf import runtime
from perf.manifest import plugin


def _albert_config(model: Dict[str, Any], seq_len: int, **overrides):
    from hivemind_tpu.models import AlbertConfig

    return AlbertConfig(
        vocab_size=model["vocab_size"], embedding_size=model["embedding_size"], hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"], num_heads=model["num_attention_heads"],
        intermediate_size=model["intermediate_size"], max_position=seq_len, **overrides,
    )


def _check_against_reference(loss_and_grad, params, config, recipe, tolerances, seed, log) -> List[str]:
    """Loss and gradient of one seeded batch of 2 sequences against the float32 reference."""
    import jax
    import jax.numpy as jnp

    from hivemind_tpu.models import make_synthetic_mlm_batch
    from perf.reference import albert as reference

    model, seq_len = config["model"], recipe["seq_len"]
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(seed % (2**31 - 1)), _albert_config(model, seq_len), 2, seq_len)
    loss, grads = loss_and_grad(params, batch)
    budget = max(1, int(seq_len * recipe["masked_loss_fraction"]))
    want_loss, want_grads = jax.jit(reference.loss_and_grad, static_argnums=(2, 3, 4))(
        params, batch, model["num_hidden_layers"], model["num_attention_heads"], budget)
    pairs = list(zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)))
    distance = math.sqrt(sum(float(jnp.sum((a.astype(jnp.float32) - b) ** 2)) for a, b in pairs)
                         / sum(float(jnp.sum(b**2)) for _, b in pairs))
    loss, want_loss = float(loss), float(want_loss)
    log(f"reference check (2 x {seq_len}): loss {loss:.4f} against {want_loss:.4f}; gradient relative L2 "
        f"distance {distance:.2e}; ln(vocab) = {math.log(model['vocab_size']):.4f}")
    faults = []
    if not abs(loss - want_loss) <= tolerances["loss_abs"]:
        faults.append(f"loss {loss} differs from the reference's {want_loss} by more than {tolerances['loss_abs']}")
    if not distance <= tolerances["grad_rel_l2"]:
        faults.append(f"gradient is {distance:.3e} from the reference's (relative L2), over {tolerances['grad_rel_l2']}")
    if not abs(loss - math.log(model["vocab_size"])) <= tolerances["init_loss_from_ln_vocab"]:
        faults.append(f"loss at initialisation {loss} is not ln(vocab) +- {tolerances['init_loss_from_ln_vocab']}")
    return faults


def run(*, config, workload, chips, seed, seconds, trace, rehearse, started, log) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.models import AlbertForMaskedLM, make_mlm_loss_fn, make_synthetic_mlm_batch
    from hivemind_tpu.moe.server.layers import lamb_with_warmup
    from hivemind_tpu.optim import Optimizer

    traffic = workload["traffic"]
    plan = plugin("traffic", traffic["generator"]).schedule(traffic, seed)
    model, recipe, tolerances = config["model"], config["recipe"], config["tolerances"]
    seq_len, per_step = recipe["seq_len"], recipe["sequences_per_peer_per_step"]
    watch, tap = runtime.CompileWatch(), runtime.LedgerTap()
    devices = jax.devices()[:chips]

    host_config = _albert_config(model, seq_len)
    host_model = AlbertForMaskedLM(host_config)
    host_loss_and_grad = jax.jit(jax.value_and_grad(make_mlm_loss_fn(host_model, recipe["masked_loss_fraction"])))
    sample_ids = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda key: host_model.init(key, sample_ids)["params"])(jax.random.PRNGKey(plan["init_seed"]))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    log(f"ALBERT hidden {model['hidden_size']}, {model['num_hidden_layers']} shared layers, vocab "
        f"{model['vocab_size']}: {count / 1e6:.1f} M parameters; {per_step} x {seq_len} tokens a peer a step; "
        f"epoch = {recipe['target_batch_size']} sequences; peers {[p['kind'] for p in plan['peers']]}")
    faults = _check_against_reference(host_loss_and_grad, params, config, recipe, tolerances, plan["init_seed"], log)

    opt_spec = recipe["optimizer"]
    common = dict(
        run_id=f"perf_{workload['name'].replace('.', '_')}", target_batch_size=recipe["target_batch_size"],
        batch_size_per_step=per_step, matchmaking_time=recipe["matchmaking_time"],
        averaging_timeout=recipe["averaging_timeout"], target_group_size=plan["target_group_size"], verbose=False,
    )
    make_optimizer = lambda: lamb_with_warmup(opt_spec["learning_rate"], opt_spec["warmup_epochs"], opt_spec["total_epochs"])

    boot = DHT(start=True)
    dhts = [boot] + [DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
                     for _ in plan["peers"][1:]]
    peers: List[Dict[str, Any]] = []
    for index, (spec, dht) in enumerate(zip(plan["peers"], dhts)):
        if spec["kind"] == "slice":
            from jax.sharding import NamedSharding, PartitionSpec as P

            from hivemind_tpu.optim import SliceOptimizer
            from hivemind_tpu.parallel import make_mesh, params_shardings

            mesh = make_mesh(**config["mesh_by_chips"][str(chips)])
            slice_model = AlbertForMaskedLM(_albert_config(model, seq_len, mesh=mesh))
            with mesh:
                step_fn = jax.jit(jax.value_and_grad(make_mlm_loss_fn(slice_model, recipe["masked_loss_fraction"])))
            placed = jax.device_put(params, params_shardings(params, mesh))
            sharding = NamedSharding(mesh, P("dp", "sp"))
            optimizer = SliceOptimizer(mesh=mesh, params=placed, dht_factory=lambda dht=dht: dht,
                                       optimizer=make_optimizer(), **common)
            peers.append(dict(kind="slice", opt=optimizer, step_fn=step_fn, mesh=mesh,
                              place=lambda batch, sharding=sharding: jax.device_put(batch, sharding)))
        else:
            optimizer = Optimizer(dht=dht, params=jax.tree_util.tree_map(jnp.copy, params),
                                  optimizer=make_optimizer(), **common)
            peers.append(dict(kind="optimizer", opt=optimizer, step_fn=host_loss_and_grad, mesh=None,
                              place=lambda batch: batch))
        peers[-1].update(index=index, rng=jax.random.PRNGKey(spec["data_seed"]), losses=[], steps=[], rounds=[])

    tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4, log=log) if trace else None
    state = {"phase": "warm", "end": math.inf, "stop_epoch": None}
    lock = threading.Lock()
    errors: List[BaseException] = []

    def one_step(peer) -> None:
        opt = peer["opt"]
        with runtime.annotate(f"peer{peer['index']}.batch"):
            peer["rng"], key = jax.random.split(peer["rng"])
            batch = peer["place"](make_synthetic_mlm_batch(key, host_config, per_step, seq_len))
        epoch_before, began = opt.local_epoch, time.monotonic()
        with runtime.annotate(f"peer{peer['index']}.loss_and_grad"):
            if peer["mesh"] is not None:
                with peer["mesh"]:
                    loss, grads = peer["step_fn"](opt.params, batch)
            else:
                loss, grads = peer["step_fn"](opt.params, batch)
        stepped = time.monotonic()
        with runtime.annotate(f"peer{peer['index']}.optimizer_step"):
            opt.step(grads, batch_size=per_step)
        done = time.monotonic()
        peer["losses"].append(loss)
        if state["phase"] == "window" and done <= state["end"]:
            peer["steps"].append(done - began)
            if tracer is not None:
                tracer.mark("steps")
            if opt.local_epoch > epoch_before:
                peer["rounds"].append(1000.0 * (done - stepped))

    def peer_loop(peer, until_epoch):
        def loop():
            try:
                while not until_epoch(peer):
                    one_step(peer)
            except BaseException as e:  # re-raised on the main thread
                errors.append(e)
        return loop

    def run_phase(until_epoch, timeout: float) -> None:
        threads = [threading.Thread(target=peer_loop(peer, until_epoch), daemon=True) for peer in peers]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout)
        if errors:
            raise errors[0]
        if any(thread.is_alive() for thread in threads):
            raise TimeoutError(f"a peer did not finish its phase within {timeout:.0f} s")

    def window_over(peer) -> bool:
        """After the window's end the peers go on (uncounted) until the epoch that is
        open closes on all of them: a peer that stopped mid-epoch would leave its
        partner alone in matchmaking."""
        if time.monotonic() < state["end"]:
            return False
        with lock:
            if state["stop_epoch"] is None:
                state["stop_epoch"] = max(p["opt"].local_epoch for p in peers) + 1
        return peer["opt"].local_epoch >= state["stop_epoch"]

    try:
        warm_began = time.monotonic()
        run_phase(lambda peer: peer["opt"].local_epoch >= plan["warm_epochs"], timeout=900.0)
        log(f"warm-up: {plan['warm_epochs']} epoch(s) closed in {time.monotonic() - warm_began:.1f} s, "
            f"{watch.count()} compilations so far")
        tap.drain()
        for peer in peers:
            peer["losses"].clear()
        compiles_before, counters_before = watch.count(), runtime.counters()
        window_began = time.monotonic()
        setup_s = window_began - started
        state.update(phase="window", end=window_began + seconds)
        if tracer is not None:
            tracer.start()
        run_phase(window_over, timeout=seconds + 300.0)
        drained_s = time.monotonic() - state["end"]
        compiles_after, counters_after = watch.count(), runtime.counters()
        records = tap.drain()
        traced = tracer.finish() if tracer is not None else {}

        losses = [float(loss) for peer in peers for loss in peer["losses"]]
        epochs = [peer["opt"].local_epoch for peer in peers]
        leaves = [jax.tree_util.tree_leaves(peer["opt"].params) for peer in peers]
        apart = max(float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max()) for a, b in zip(*leaves[:2])) if len(peers) > 1 else 0.0
        if not all(np.isfinite(losses)):
            faults.append("a loss in the window was not finite")
        if len(set(epochs)) != 1:
            faults.append(f"the peers ended at different epochs {epochs}")
        if not apart <= tolerances["peer_params_abs"]:
            faults.append(f"the peers' parameters are {apart:.2e} apart, over {tolerances['peer_params_abs']}")
        if compiles_after != compiles_before:
            faults.append(f"{compiles_after - compiles_before} compilation(s) inside the window: the warm-up missed a shape")
        memory_peak = runtime.memory_peak_bytes(devices, log)
    finally:
        tap.close()
        for peer in peers:
            peer["opt"].shutdown()
        for dht in reversed(dhts):
            dht.shutdown()

    # rounds: one gradient round per peer per epoch is what the swarm owed; a round
    # failed if its epoch fell back to local gradients or its group was short
    epochs_closed = [r for r in records["epoch"]]
    degraded = [r for r in epochs_closed if r.get("averaged_ok") is False]
    short = [r for r in records["round"] if (r.get("group_size") or 0) < len(peers)]
    steps = sum(len(peer["steps"]) for peer in peers)
    log(f"window {seconds:.1f} s (+{drained_s:.1f} s to close the open epoch): {steps} steps, "
        f"{len(epochs_closed)} epoch transitions, {len(records['round'])} all-reduce rounds "
        f"({len(short)} short of {len(peers)} peers), {len(degraded)} fell back to local gradients; "
        f"last loss {losses[-1] if losses else float('nan'):.3f}; peers {apart:.2e} apart; set-up {setup_s:.1f} s")
    for fault in faults:
        log(f"FAULT: {fault}")
    exposed = [ms for peer in peers for ms in peer["rounds"]]
    return {
        "correct": not faults,
        "attempted": len(records["round"]) + len(degraded),
        "failed": len(degraded) + len(short),
        "setup_s": setup_s,
        "window_s": seconds,
        "counts": {"tokens": steps * per_step * seq_len, "steps": steps, "rounds": len(exposed)},
        "samples": {"round_exposed_ms": exposed, "step_ms": [1000.0 * s for peer in peers for s in peer["steps"]]},
        "counters": {"before": counters_before, "after": counters_after},
        "rounds": records["round"],
        "epochs": epochs_closed,
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
