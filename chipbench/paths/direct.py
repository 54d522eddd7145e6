"""Path plug-in ``direct``: one-hop sync that bypasses the volume and the
put protocol (`put_state_dict(direct=True)`, a refresh after the first;
`get_state_dict(direct=True)`). WHICH rung serves is the store's own
decision (`device_transfer.serves`): the device rung where the runtime
serves the platform, host staging buffers where it does not (a TPU, PR 21).
``check`` holds the store to the rung it chose. The path has no version
numbers of its own: it counts its puts."""

KEY = "policy/direct"


def _counter(name: str) -> float:
    import torchstore_tpu as ts

    series = ts.metrics_snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series)


class Path:
    def __init__(self, store_name: str, mix: dict):
        self._store_name = store_name
        self._puts = 0
        self._pulls_before = 0.0

    async def open(self) -> None:
        pass

    async def publish(self, tree) -> int:
        import torchstore_tpu as ts

        await ts.put_state_dict(
            KEY, {"params": tree}, direct=True, store_name=self._store_name
        )
        self._puts += 1
        return self._puts - 1

    async def acquire(self, targets):
        import torchstore_tpu as ts

        self._pulls_before = _counter("ts_device_pull_ops_total")
        got = await ts.get_state_dict(
            KEY,
            user_state_dict={"params": targets},
            direct=True,
            store_name=self._store_name,
        )
        return got["params"], self._puts - 1

    async def check(self, tree) -> list[str]:
        """The rung the source published and the rung the pull took are the
        one `device_transfer.serves` names for these arrays."""
        import jax

        import torchstore_tpu as ts
        from torchstore_tpu.transport import device_transfer

        leaves = jax.tree.leaves(tree)
        on_device_rung = all(map(device_transfer.serves, leaves))
        rung = "device" if on_device_rung else "host-staged"
        published = await ts.get(f"{KEY}/rank_0", store_name=self._store_name)
        problems = []
        handles = len(published["handles"])
        if (published.get("device") is not None) != on_device_rung or handles != (
            0 if on_device_rung else len(leaves)
        ):
            problems.append(
                f"expected the {rung} rung, but the source published {handles} "
                f"host handles and device info "
                f"{'present' if published.get('device') else 'absent'}"
            )
        pulled = _counter("ts_device_pull_ops_total") > self._pulls_before
        if pulled != on_device_rung:
            problems.append(f"ts_device_pull_ops_total disagrees with the {rung} rung")
        return problems

    async def close(self) -> None:
        pass


def make(store_name: str, mix: dict) -> Path:
    return Path(store_name, mix)
