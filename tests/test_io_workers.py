"""Multiprocess DataLoader workers (reference: paddle.io.DataLoader
num_workers>0 — _DataLoaderIterMultiProcess, SURVEY.md §2.3 paddle.io)."""

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset


class _DS(Dataset):
    def __init__(self, n=40):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((3,), i, np.float32), np.int64(i)


def test_multiprocess_workers_order_and_values():
    # timeout: a forked worker wedged on a lock it inherited (rare, after
    # jax's threads exist) is this test's failure in a minute, not a hang
    loader = DataLoader(_DS(), batch_size=4, num_workers=2, shuffle=False, timeout=60)
    seen = []
    for xb, yb in loader:
        assert xb.shape == [4, 3]
        seen.extend(yb.numpy().tolist())
    assert seen == list(range(40)), "batches must come back in order"


def test_multiprocess_worker_error_surfaces():
    class Bad(_DS):
        def __getitem__(self, i):
            if i == 7:
                raise ValueError("poison sample")
            return super().__getitem__(i)

    import pytest

    loader = DataLoader(Bad(), batch_size=4, num_workers=2, timeout=60)
    with pytest.raises(RuntimeError, match="worker failed"):
        list(loader)


def test_thread_fallback_still_works():
    loader = DataLoader(_DS(8), batch_size=4, num_workers=2, use_shared_memory=False)
    out = [y.numpy().tolist() for _, y in loader]
    assert out == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_thread_worker_error_propagates_promptly():
    """A dying prefetch thread must poison-pill the queue — the ORIGINAL
    exception surfaces at the consumer instead of a silent early epoch end."""

    class Bad(_DS):
        def __getitem__(self, i):
            if i == 5:
                raise ValueError("boom at 5")
            return super().__getitem__(i)

    import pytest

    loader = DataLoader(Bad(16), batch_size=2, num_workers=2, use_shared_memory=False)
    consumed = 0
    with pytest.raises(ValueError, match="boom at 5"):
        for _ in loader:
            consumed += 1
    assert consumed < 8, "the epoch must not look complete after the crash"


def test_prefetch_to_device_round_trip():
    """Double-buffered H2D prefetch must be value/order transparent — the
    batches just arrive already device-resident."""
    loader = DataLoader(_DS(24), batch_size=4, shuffle=False, prefetch_to_device=True)
    seen = []
    for xb, yb in loader:
        assert xb.shape == [4, 3]
        # the payload is a committed jax array, not a host numpy buffer
        assert hasattr(xb._raw, "block_until_ready")
        np.testing.assert_array_equal(xb.numpy()[:, 0], yb.numpy().astype(np.float32))
        seen.extend(yb.numpy().tolist())
    assert seen == list(range(24))
    assert loader._prefetch_hwm >= 1


def test_prefetch_to_device_mid_epoch_resume():
    """Exactly-once resume is counted at the CONSUMER: batches sitting in
    the device prefetch queue when the checkpoint is taken are replayed,
    consumed ones are not."""
    loader = DataLoader(_DS(16), batch_size=4, shuffle=False, prefetch_to_device=2)
    it = iter(loader)
    next(it)
    next(it)  # the prefetcher is ahead of us by now
    state = loader.state_dict()
    assert state["batches_consumed"] == 2
    del it

    fresh = DataLoader(_DS(16), batch_size=4, shuffle=False, prefetch_to_device=2)
    fresh.set_state_dict(state)
    first = next(iter(fresh))[1].numpy().tolist()
    assert first == [8, 9, 10, 11], "resume must start at the exact next batch"


def test_prefetch_to_device_error_propagates():
    class Bad(_DS):
        def __getitem__(self, i):
            if i == 9:
                raise ValueError("boom at 9")
            return super().__getitem__(i)

    import pytest

    loader = DataLoader(Bad(16), batch_size=4, shuffle=False, prefetch_to_device=True)
    with pytest.raises(ValueError, match="boom at 9"):
        list(loader)


def test_thread_worker_injected_fault_propagates():
    # the registered dataloader.next fault fires INSIDE the prefetch
    # thread — it must cross the queue with its type intact
    from paddle_tpu import fault

    fault.arm("dataloader.next:1")
    try:
        loader = DataLoader(_DS(8), batch_size=2, num_workers=2, use_shared_memory=False)
        import pytest

        with pytest.raises(fault.InjectedFault):
            list(loader)
    finally:
        fault.disarm()
