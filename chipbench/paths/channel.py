"""Path plug-in ``channel``: the versioned weight channel through a real
storage volume (`WeightPublisher.publish` / `WeightSubscriber.acquire`):
D2H, shm transport, volume, shm, H2D."""

CHANNEL = "policy"


class Path:
    def __init__(self, store_name: str, mix: dict):
        self._store_name = store_name
        self._timeout = float(mix["acquire_timeout_s"])
        self._publisher = None
        self._subscriber = None

    async def open(self) -> None:
        import torchstore_tpu as ts

        self._publisher = ts.WeightPublisher(CHANNEL, store_name=self._store_name)
        self._subscriber = ts.WeightSubscriber(CHANNEL, store_name=self._store_name)

    async def publish(self, tree) -> int:
        """Returns when the version is committed; its number."""
        return await self._publisher.publish({"params": tree})

    async def acquire(self, targets):
        """(tree, version). The arrays may still be in flight to the device:
        the caller waits for them."""
        got, version = await self._subscriber.acquire(
            user_state_dict={"params": targets}, timeout=self._timeout
        )
        return got["params"], version

    async def check(self, tree) -> list[str]:
        return []

    async def close(self) -> None:
        if self._publisher is not None:
            await self._publisher.close(delete=True)


def make(store_name: str, mix: dict) -> Path:
    return Path(store_name, mix)
