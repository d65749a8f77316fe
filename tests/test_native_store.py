"""Native arena allocator + arena-backed object store tests.

Reference capability under test: the plasma allocator/object-store core
(src/ray/object_manager/plasma/plasma_allocator.cc, object_store.cc) —
here the C++ boundary-tag arena in ray_tpu/_native/arena.cc and its
integration behind ShmObjectStore.
"""

import os

import pytest

from ray_tpu import _native

pytestmark = pytest.mark.skipif(
    not _native.available(), reason="native toolchain unavailable"
)


@pytest.fixture
def arena(tmp_path):
    # arenas work on any filesystem; tmp keeps /dev/shm clean under pytest
    a = _native.Arena(str(tmp_path / "arena"), capacity=1 << 20, create=True)
    yield a
    a.close()
    try:
        a.unlink()
    except OSError:
        pass


def _oid(i: int) -> bytes:
    return bytes([i]) * 24


class TestAllocator:
    def test_alloc_is_aligned_and_validates(self, arena):
        off = arena.alloc(_oid(1), 1000)
        assert off > 0 and off % 64 == 0
        assert arena.validate(_oid(1), off, 1000)
        assert not arena.validate(_oid(2), off, 1000)   # wrong id
        assert not arena.validate(_oid(1), off, 999)    # wrong size

    def test_free_scrubs_header_and_coalesces(self, arena):
        offs = [arena.alloc(_oid(i), 10_000) for i in range(1, 6)]
        assert all(o > 0 for o in offs)
        for o in offs:
            assert arena.free(o)
        assert arena.used() == 0
        assert arena.num_free_blocks() == 1  # fully coalesced
        assert not arena.validate(_oid(1), offs[0], 10_000)  # scrubbed

    def test_first_fit_reuses_freed_hole(self, arena):
        a = arena.alloc(_oid(1), 10_000)
        b = arena.alloc(_oid(2), 10_000)
        assert a > 0 and b > 0
        arena.free(a)
        c = arena.alloc(_oid(3), 5_000)
        assert c == a  # the freed hole is first-fit reused

    def test_exhaustion_returns_minus_one(self, arena):
        assert arena.alloc(_oid(1), (1 << 20)) == -1  # header doesn't fit
        ok = arena.alloc(_oid(1), (1 << 20) - 64)
        assert ok > 0
        assert arena.alloc(_oid(2), 64) == -1

    def test_fragmentation_probe(self, arena):
        offs = [arena.alloc(_oid(i), 100_000) for i in range(1, 9)]
        arena.free(offs[1])
        arena.free(offs[3])
        # two ~100k holes + the arena tail: three disjoint free blocks
        assert arena.num_free_blocks() == 3
        assert arena.largest_free() >= 100_000
        # a 200k allocation cannot fit either hole -> must land in the tail
        tail = arena.alloc(_oid(9), 200_000)
        assert tail > offs[7]

    def test_double_free_rejected(self, arena):
        off = arena.alloc(_oid(1), 128)
        assert arena.free(off)
        assert not arena.free(off)
        assert not arena.free(12345)  # never-allocated offset

    def test_attach_sees_writes(self, arena, tmp_path):
        off = arena.alloc(_oid(7), 256)
        arena.slice(off, 256)[:] = b"z" * 256
        other = _native.Arena(str(tmp_path / "arena"))
        try:
            assert bytes(other.slice(off, 256)) == b"z" * 256
            assert other.validate(_oid(7), off, 256)
        finally:
            other.close()


class TestArenaStore:
    @pytest.fixture
    def store(self, tmp_path):
        from ray_tpu.core.shm_store import ShmObjectStore

        s = ShmObjectStore(
            "cafef00d", capacity_bytes=1 << 20,
            spill_dir=str(tmp_path / "spill"), backend="arena",
        )
        assert s.backend == "arena"
        yield s
        s.cleanup()

    def _write(self, store, oid, data: bytes) -> int:
        from ray_tpu.core.shm_store import ShmWriter

        off = store.reserve(oid, len(data))
        assert off is not None and off > 0
        w = ShmWriter(oid, len(data), store.node_suffix, offset=off)
        w.buffer[:] = data
        w.seal()
        store.seal(oid)
        return off

    def test_write_read_roundtrip(self, store):
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.shm_store import ShmReader

        oid = ObjectID.from_random()
        off = self._write(store, oid, b"hello arena" * 100)
        r = ShmReader(oid, 1100, store.node_suffix, offset=off)
        assert bytes(r.buffer) == b"hello arena" * 100
        assert store.offset(oid) == off

    def test_evicted_slot_fails_validation(self, store):
        """A reader holding a stale offset must see 'missing', never another
        object's bytes (the in-arena header check)."""
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.shm_store import ShmReader

        oid = ObjectID.from_random()
        off = self._write(store, oid, b"a" * 600_000)
        # force eviction by filling the store past capacity
        oid2 = ObjectID.from_random()
        self._write(store, oid2, b"b" * 600_000)
        assert store.offset(oid) is None  # spilled (or dropped) under pressure
        with pytest.raises(FileNotFoundError):
            ShmReader(oid, 600_000, store.node_suffix, offset=off)

    def test_spill_and_restore_reallocates(self, store):
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.shm_store import ShmReader

        oid = ObjectID.from_random()
        payload = os.urandom(600_000)
        self._write(store, oid, payload)
        oid2 = ObjectID.from_random()
        self._write(store, oid2, b"x" * 600_000)  # evicts oid to spill
        assert store.offset(oid) is None
        size = store.ensure_local(oid)  # restore from disk
        assert size == len(payload)
        off = store.offset(oid)
        assert off is not None
        r = ShmReader(oid, size, store.node_suffix, offset=off)
        assert bytes(r.buffer) == payload

    def test_spilling_a_dep_undoes_the_hole_it_split(self, store):
        """What the agent's busy requeue leans on: an object pinned in the
        middle of the arena leaves free bytes enough, in holes that are each
        too small; nothing evictable merges them. Once unpinned and sent to
        spill BY NAME it comes back first-fit at the low end, and the same
        reservation fits."""
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.shm_store import ObjectStoreFullError

        e = (1 << 20) // 8
        a, dep = ObjectID.from_random(), ObjectID.from_random()
        self._write(store, a, b"a" * (3 * e - 4096))
        self._write(store, dep, b"d" * (e - 4096))
        store.pin(dep)
        store.delete(a)  # [hole 3/8][dep, pinned 1/8][hole 4/8]
        out = ObjectID.from_random()
        with pytest.raises(ObjectStoreFullError, match="fragmented"):
            store.reserve(out, 5 * e)
        assert not store.spill(dep), "a pinned object must stay"
        store.unpin(dep)
        assert store.spill(dep) and store.offset(dep) is None
        assert store.ensure_local(dep) == e - 4096
        store.pin(dep)  # [dep 1/8][hole 7/8]
        assert store.reserve(out, 5 * e) > store.offset(dep)

    def test_delete_frees_arena_space(self, store):
        from ray_tpu.core.ids import ObjectID

        oid = ObjectID.from_random()
        self._write(store, oid, b"d" * 10_000)
        used = store.usage()
        assert used["arena_used"] > 0
        store.delete(oid)
        assert store.usage()["arena_used"] == 0

    def test_usage_reports_backend(self, store):
        u = store.usage()
        assert u["backend"] == "arena"
        assert "arena_largest_free" in u

    def test_abort_quarantines_block_until_grace(self, store, monkeypatch):
        """An aborted reservation's block must not re-enter circulation
        until the grace period passes (zombie-writer protection)."""
        from ray_tpu.core.config import config
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.shm_store import ShmWriter

        monkeypatch.setattr(config, "arena_abort_quarantine_s", 60.0)
        oid = ObjectID.from_random()
        off = store.reserve(oid, 1000)
        w = ShmWriter(oid, 1000, store.node_suffix, offset=off)
        store.abort(oid)
        # the zombie writer fails its seal (header scrubbed at abort) ...
        w.buffer[:] = b"z" * 1000
        with pytest.raises(FileNotFoundError):
            w.seal()
        # ... and a new reservation does NOT land on the quarantined block
        oid2 = ObjectID.from_random()
        off2 = store.reserve(oid2, 1000)
        assert off2 != off
        # once the grace period expires, the block is reusable again
        monkeypatch.setattr(config, "arena_abort_quarantine_s", 0.0)
        store._quarantine = [(0.0, off, 1000)]
        oid3 = ObjectID.from_random()
        off3 = store.reserve(oid3, 1000)
        assert off3 == off

    def test_read_bytes_detects_mid_copy_eviction(self, store):
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.shm_store import ShmReader

        oid = ObjectID.from_random()
        self._write(store, oid, b"r" * 1000)
        r = ShmReader(oid, 1000, store.node_suffix, offset=store.offset(oid))
        assert r.read_bytes() == b"r" * 1000  # normal path revalidates clean
        store.delete(oid)  # slot freed (header scrubbed) while reader exists
        with pytest.raises(FileNotFoundError):
            r.read_bytes()


class TestChannel:
    """Seqlock mutable-object channel (channel.cc): cross-process versioned
    acquire/release (reference: experimental_mutable_object_manager.h:48)."""

    @pytest.fixture
    def chan(self, tmp_path):
        import ctypes
        import mmap

        from ray_tpu._native import lib

        L = lib()
        path = str(tmp_path / "chan")
        size = 4096
        fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o600)
        os.ftruncate(fd, size)
        mm = mmap.mmap(fd, size)
        os.close(fd)
        base = ctypes.addressof(ctypes.c_char.from_buffer(mm))
        L.rtpu_chan_init(base)
        yield L, mm, base, path, size
        del base
        try:
            mm.close()
        except BufferError:
            pass

    def test_write_read_versions(self, chan):
        import ctypes

        L, mm, base, _, _ = chan
        hdr = L.rtpu_chan_header_size()
        assert L.rtpu_chan_version(base) == 0
        v = L.rtpu_chan_write_acquire(base, 0, 1000)
        assert v == 1
        memoryview(mm)[hdr:hdr + 3] = b"abc"
        L.rtpu_chan_write_release(base, 3)
        ln = ctypes.c_uint64()
        got = L.rtpu_chan_read_acquire(base, 0, ctypes.byref(ln), 1000)
        assert got == 1 and ln.value == 3
        assert bytes(memoryview(mm)[hdr:hdr + 3]) == b"abc"
        assert L.rtpu_chan_read_validate(base, 1) == 1

    def test_read_blocks_until_new_version_and_times_out(self, chan):
        import ctypes

        L, _, base, _, _ = chan
        ln = ctypes.c_uint64()
        assert L.rtpu_chan_read_acquire(base, 0, ctypes.byref(ln), 50) == -1

    def test_lossless_mode_cross_process(self, chan):
        """Writer in a subprocess; depth-1 queue: every version delivered."""
        import ctypes
        import multiprocessing as mp

        L, mm, base, path, size = chan
        hdr = L.rtpu_chan_header_size()

        def writer(path, size):
            import ctypes
            import mmap as mmap_mod

            from ray_tpu._native import lib as lib_fn

            L2 = lib_fn()
            fd = os.open(path, os.O_RDWR)
            m = mmap_mod.mmap(fd, size)
            os.close(fd)
            b = ctypes.addressof(ctypes.c_char.from_buffer(m))
            h = L2.rtpu_chan_header_size()
            for i in range(5):
                v = L2.rtpu_chan_write_acquire(b, 1, 10_000)
                assert v == i + 1
                payload = f"msg-{i}".encode()
                memoryview(m)[h:h + len(payload)] = payload
                L2.rtpu_chan_write_release(b, len(payload))
            L2.rtpu_chan_close(b)

        p = mp.get_context("fork").Process(target=writer, args=(path, size))
        p.start()
        got, last = [], 0
        while True:
            ln = ctypes.c_uint64()
            v = L.rtpu_chan_read_acquire(base, last, ctypes.byref(ln), 15_000)
            if v == -2:
                break
            assert v > 0
            got.append(bytes(memoryview(mm)[hdr:hdr + ln.value]))
            assert L.rtpu_chan_read_validate(base, v)
            L.rtpu_chan_read_ack(base, 0, v)
            last = v
        p.join(timeout=30)
        assert got == [f"msg-{i}".encode() for i in range(5)]

    def test_close_unblocks_readers(self, chan):
        import ctypes

        L, _, base, _, _ = chan
        L.rtpu_chan_close(base)
        ln = ctypes.c_uint64()
        assert L.rtpu_chan_read_acquire(base, 0, ctypes.byref(ln), 5000) == -2
        assert L.rtpu_chan_is_closed(base)
