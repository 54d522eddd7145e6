"""Loop plug-in ``closed_loop_sync``: a trainer and a generator that each
wait for the other, as in upstream's `example/torchstore_rl.py`. One cycle:

    train     trainer step, block_until_ready            (not timed)
    publish   path.publish(trainer's params)             -> publish_s
    acquire   path.acquire(generator's resident params)  -> acquire_s
              ... until block_until_ready on every leaf   (its tail: h2d_tail)
    check     version, placement, bitwise equality with the reference,
              the trainer's and the path's own checks    (not timed)

``sync_s`` runs from the start of publish to the end of acquire. The first
acquire lands in bare specs, every later one in the arrays the generator
holds. Warm-up cycles (set-up) run until one creates no shm segment and
compiles nothing. Then rounds of ``cycles_per_sample`` cycles start until
``seconds`` have passed, and the round in flight finishes: a window is a
whole number of rounds, and one sample of a metric is its mean over a round
(the mix's file says why a round is more than one cycle).

The mix's parameters: ``trainer_rules`` / ``generator_rules`` (names of the
configuration's rule sets), ``warmup.max_cycles``, ``acquire_timeout_s``,
``cycles_per_sample``."""

import statistics
import time
import traceback


async def drive(session) -> dict:
    import jax

    from chipbench import check, trees

    trainer, path, mix = session.trainer, session.path, session.mix
    target_shardings = session.generator_shardings
    n_leaves = len(jax.tree.leaves(trainer.params))
    # What the next acquire lands in: bare specs first, then the generator's
    # own arrays, as a running generator would.
    generator = trees.as_targets(trainer.params, target_shardings)
    have_weights = False

    async def cycle(index: int) -> list[str]:
        """One whole cycle; the problems its checks found."""
        nonlocal generator, have_weights
        with session.phase("train", index):
            trainer.step()
            jax.block_until_ready(trainer.params)
        problems = []
        with session.phase("publish", index):
            version = await path.publish(trainer.params)
        with session.phase("acquire", index):
            got, got_version = await path.acquire(generator)
            with session.phase("h2d_tail", index):
                got = jax.block_until_ready(got)
        with session.phase("check", index):
            # Sub-phases only name the check's own time in a traced run.
            if have_weights:
                # Against the version the generator held until now: had the
                # step left a leaf as it was, a stale read of it would pass.
                with session.phase("check.changed", index):
                    changed = check.mismatched_leaves(generator, got)
                if len(changed) != n_leaves:
                    problems.append(
                        f"{n_leaves - len(changed)} leaves are bitwise what the "
                        "version before held"
                    )
            generator, have_weights = got, True
            if got_version != version:
                problems.append(f"acquired v{got_version}, published v{version}")
            with session.phase("check.placed", index):
                problems += check.misplaced_leaves(got, target_shardings)
            with session.phase("check.reference", index):
                reference = jax.block_until_ready(session.reference())
            with session.phase("check.equal", index):
                bad = check.mismatched_leaves(got, reference)
            if bad:
                problems.append(
                    f"v{version}: {len(bad)} leaves differ from the reference: {bad[:5]}"
                )
            with session.phase("check.trainer", index):
                problems += trainer.check(got, reference)
            del reference
            problems += await path.check(trainer.params)
        return problems

    index = 0
    while True:
        segments, compiles = await session.segments_created(), session.compile_requests()
        problems = await cycle(index)
        index += 1
        if problems:
            raise RuntimeError(f"warm-up cycle {index - 1} failed: {problems}")
        if (
            await session.segments_created() == segments
            and session.compile_requests() == compiles
        ):
            break
        if index >= mix["warmup"]["max_cycles"]:
            raise RuntimeError(
                f"after {index} warm-up cycles a cycle still creates shm "
                "segments or compiles"
            )

    result = {"attempted": 0, "failed": 0, "problems": [], "warmup_cycles": index}
    session.begin_window()
    started = time.perf_counter()
    while (
        time.perf_counter() - started < session.seconds
        or result["attempted"] % mix["cycles_per_sample"]
    ):
        session.cycle_begins(result["attempted"])
        result["attempted"] += 1
        try:
            problems = await cycle(index)
        except Exception:  # noqa: BLE001 - the boundary: the cycle failed, report it
            traceback.print_exc()
            # The arrays' state is unknown after a failed call (donated,
            # half landed): no further cycle can be trusted.
            result["failed"] += 1
            result["problems"].append(f"cycle {index} raised (see stderr)")
            break
        session.cycle_ends(result["attempted"] - 1)
        if problems:
            result["failed"] += 1
            result["problems"] += [f"cycle {index}: {p}" for p in problems]
        index += 1
    session.end_window()
    return result


def samples(phases: list[dict], mix: dict) -> dict[str, list[float]]:
    """The end-to-end readings of the window, one per round, from the phases
    the session recorded: the names are this loop's metrics."""
    by_cycle: dict[int, dict[str, dict]] = {}
    for p in phases:
        by_cycle.setdefault(p["cycle"], {})[p["name"]] = p
    whole = [c for _, c in sorted(by_cycle.items()) if "publish" in c and "acquire" in c]
    per_round = mix["cycles_per_sample"]
    out: dict[str, list[float]] = {"publish_s": [], "acquire_s": [], "sync_s": []}
    for i in range(0, len(whole) - per_round + 1, per_round):
        cycles = whole[i : i + per_round]
        for name, first, last in (
            ("publish_s", "publish", "publish"),
            ("acquire_s", "acquire", "acquire"),
            ("sync_s", "publish", "acquire"),
        ):
            out[name].append(
                statistics.fmean(c[last]["end"] - c[first]["start"] for c in cycles)
            )
    return out
