"""UID service tests (ref: test/uid/TestUniqueId.java)."""

import threading

import pytest

from opentsdb_tpu.core.uid import (FailedToAssignUniqueIdError, NoSuchUniqueId,
                                   NoSuchUniqueName, UidRegistry, UniqueId)


class TestUniqueId:
    def test_assignment_is_monotonic(self):
        uid = UniqueId("metric")
        assert uid.get_or_create_id("a") == 1
        assert uid.get_or_create_id("b") == 2
        assert uid.get_or_create_id("a") == 1

    def test_lookup_missing_raises(self):
        uid = UniqueId("metric")
        with pytest.raises(NoSuchUniqueName):
            uid.get_id("nope")
        with pytest.raises(NoSuchUniqueId):
            uid.get_name(42)

    def test_bytes_codec(self):
        uid = UniqueId("metric", width=3)
        i = uid.get_or_create_id("m")
        assert uid.int_to_uid(i) == b"\x00\x00\x01"
        assert uid.uid_to_int(b"\x00\x00\x01") == 1
        assert uid.get_name(b"\x00\x00\x01") == "m"

    def test_width_exhaustion(self):
        uid = UniqueId("metric", width=1)
        for i in range(255):
            uid.get_or_create_id(f"m{i}")
        with pytest.raises(FailedToAssignUniqueIdError):
            uid.get_or_create_id("one-too-many")

    def test_explicit_assign_conflicts(self):
        uid = UniqueId("metric")
        uid.assign_id("m")
        with pytest.raises(FailedToAssignUniqueIdError):
            uid.assign_id("m")

    def test_rename(self):
        uid = UniqueId("metric")
        i = uid.get_or_create_id("old")
        uid.rename("old", "new")
        assert uid.get_id("new") == i
        assert uid.get_name(i) == "new"
        with pytest.raises(NoSuchUniqueName):
            uid.get_id("old")

    def test_random_ids(self):
        uid = UniqueId("metric", random_ids=True)
        ids = {uid.get_or_create_id(f"m{i}") for i in range(100)}
        assert len(ids) == 100
        assert all(1 <= i <= uid.max_possible_id for i in ids)

    def test_filter_veto(self):
        uid = UniqueId("metric",
                       filter_fn=lambda kind, name: not name.startswith("x"))
        uid.get_or_create_id("ok")
        with pytest.raises(FailedToAssignUniqueIdError):
            uid.get_or_create_id("xbad")

    def test_suggest(self):
        uid = UniqueId("metric")
        for name in ("sys.cpu.user", "sys.cpu.sys", "sys.mem.free", "proc.x"):
            uid.get_or_create_id(name)
        assert uid.suggest("sys.cpu") == ["sys.cpu.sys", "sys.cpu.user"]
        assert uid.suggest("sys", max_results=2) == \
            ["sys.cpu.sys", "sys.cpu.user"]

    def test_concurrent_assignment_no_duplicates(self):
        """The atomic-increment + CAS dedupe contract
        (ref: UniqueId.java:117 pending-assignment map)."""
        uid = UniqueId("tagv")
        results: list[int] = []

        def worker():
            for i in range(200):
                results.append(uid.get_or_create_id(f"v{i % 50}"))

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(uid) == 50
        # every name resolved to exactly one id everywhere
        by_name = {}
        for i in range(50):
            by_name[f"v{i}"] = uid.get_id(f"v{i}")
        assert len(set(by_name.values())) == 50


class TestUidRegistry:
    def test_tsuid(self):
        reg = UidRegistry()
        m = reg.metrics.get_or_create_id("sys.cpu.user")
        k = reg.tag_names.get_or_create_id("host")
        v = reg.tag_values.get_or_create_id("web01")
        tsuid = reg.tsuid(m, [(k, v)])
        assert tsuid == b"\x00\x00\x01\x00\x00\x01\x00\x00\x01"
        assert tsuid.hex().upper() == "000001000001000001"

    def test_by_kind(self):
        reg = UidRegistry()
        assert reg.by_kind("metric") is reg.metrics
        assert reg.by_kind("tagk") is reg.tag_names
        assert reg.by_kind("tagv") is reg.tag_values
        with pytest.raises(ValueError):
            reg.by_kind("bogus")


class TestUidReferenceMatrix:
    """The remaining TestUniqueId.java scenario classes, table-driven
    (ctor validation, codec edges, filter/race/overflow behavior)."""

    def test_ctor_validation(self):
        # (ref: testCtorZeroWidth/NegativeWidth/EmptyKind/LargeWidth)
        with pytest.raises(ValueError):
            UniqueId("metric", 0)
        with pytest.raises(ValueError):
            UniqueId("metric", -1)
        with pytest.raises(ValueError):
            UniqueId("metric", 9)
        with pytest.raises(ValueError):
            UniqueId("", 3)

    def test_kind_and_width_accessors(self):
        u = UniqueId("tagk", 3)
        assert u.kind == "tagk" and u.width == 3

    def test_uid_bytes_roundtrip_edges(self):
        # (ref: uidToString/uidToString255/uidToStringZeros)
        u = UniqueId("metric", 3)
        for v in (0, 1, 255, 256, 65535, 2 ** 24 - 1):
            b = u.int_to_uid(v)
            assert len(b) == 3
            assert u.uid_to_int(b) == v
        assert u.int_to_uid(0) == b"\x00\x00\x00"
        assert u.int_to_uid(2 ** 24 - 1) == b"\xff\xff\xff"

    def test_uid_wrong_length_rejected(self):
        # (ref: stringToUidWidth/stringToUidWidth2)
        u = UniqueId("metric", 3)
        with pytest.raises(ValueError):
            u.uid_to_int(b"\x00")
        with pytest.raises(ValueError):
            u.uid_to_int(b"\x00\x00\x00\x00")

    def test_get_name_nonexistent(self):
        # (ref: getNameForNonexistentId)
        u = UniqueId("metric", 3)
        with pytest.raises(LookupError):
            u.get_name(12345)

    def test_get_id_nonexistent(self):
        # (ref: getIdForNonexistentName)
        u = UniqueId("metric", 3)
        with pytest.raises(LookupError):
            u.get_id("nosuch")

    def test_get_or_create_idempotent(self):
        # (ref: getOrCreateIdWithExistingId)
        u = UniqueId("metric", 3)
        a = u.get_or_create_id("m")
        assert u.get_or_create_id("m") == a
        assert u.max_id() == a

    def test_overflow_exhaustion(self):
        # (ref: getOrCreateIdWithOverflow) width-1 space has 255 ids
        u = UniqueId("metric", 1)
        for i in range(255):
            u.get_or_create_id(f"m{i}")
        with pytest.raises(FailedToAssignUniqueIdError):
            u.get_or_create_id("one-too-many")

    def test_random_collision_retries(self):
        # (ref: getOrCreateIdRandomCollision) small space forces
        # collisions; every id must still be unique
        u = UniqueId("metric", 1, random_ids=True)
        ids = {u.get_or_create_id(f"m{i}") for i in range(100)}
        assert len(ids) == 100

    def test_suggest_no_match_and_matches(self):
        # (ref: suggestWithNoMatch/suggestWithMatches)
        u = UniqueId("metric", 3)
        for n in ("sys.cpu.user", "sys.cpu.system", "net.bytes"):
            u.get_or_create_id(n)
        assert u.suggest("zz") == []
        assert u.suggest("sys.cpu") == ["sys.cpu.system",
                                        "sys.cpu.user"]
        assert u.suggest("", max_results=2) == ["net.bytes",
                                                "sys.cpu.system"]

    def test_rename_collision_rejected(self):
        # (ref: renameIdTakenName analogue)
        u = UniqueId("metric", 3)
        u.get_or_create_id("a")
        u.get_or_create_id("b")
        with pytest.raises(FailedToAssignUniqueIdError):
            u.rename("a", "b")

    def test_rename_missing_rejected(self):
        u = UniqueId("metric", 3)
        with pytest.raises(LookupError):
            u.rename("ghost", "x")

    def test_tsuid_tagk_sort_order(self):
        # (ref: TSUID layout: metric + sorted (tagk, tagv) pairs)
        from opentsdb_tpu.core.uid import UidRegistry
        reg = UidRegistry()
        m = reg.metrics.get_or_create_id("m")
        k1 = reg.tag_names.get_or_create_id("zz")
        k2 = reg.tag_names.get_or_create_id("aa")
        v = reg.tag_values.get_or_create_id("x")
        t = reg.tsuid(m, [(k1, v), (k2, v)])
        # k2 ("aa", assigned second => id 2) sorts by tagk ID
        assert t == (reg.metrics.int_to_uid(m)
                     + reg.tag_names.int_to_uid(min(k1, k2))
                     + reg.tag_values.int_to_uid(v)
                     + reg.tag_names.int_to_uid(max(k1, k2))
                     + reg.tag_values.int_to_uid(v))


class TestGeneration:
    """What versions a cache of names by id (the plan index's name
    tables, PR 41): every change of what an ASSIGNED id is called."""

    def test_an_assignment_leaves_it(self):
        u = UniqueId("tagv")
        before = u.generation
        u.get_or_create_id("a")
        u.assign_id("b")
        assert u.get_or_create_id("a") == 1
        assert u.generation == before

    @pytest.mark.parametrize("change", [
        lambda u: u.rename("a", "c"),
        lambda u: u.delete("a"),
        lambda u: u.load({"a": 1, "b": 2}, 2),
    ], ids=["rename", "delete", "load"])
    def test_a_change_of_an_assigned_name_moves_it(self, change):
        u = UniqueId("tagv")
        u.get_or_create_id("a")
        u.get_or_create_id("b")
        before = u.generation
        change(u)
        assert u.generation > before

    @pytest.mark.parametrize("change", [
        lambda u: u.rename("ghost", "c"),
        lambda u: u.rename("a", "b"),
        lambda u: u.delete("ghost"),
    ], ids=["rename-missing", "rename-taken", "delete-missing"])
    def test_a_refused_change_leaves_it(self, change):
        u = UniqueId("tagv")
        u.get_or_create_id("a")
        u.get_or_create_id("b")
        before = u.generation
        with pytest.raises((LookupError, FailedToAssignUniqueIdError)):
            change(u)
        assert u.generation == before

    def test_load_replaces_the_dictionary(self):
        u = UniqueId("tagv")
        u.get_or_create_id("old")
        u.suggest("o")                  # fills the sorted index
        u.load({"x": 3, "y": "7"}, 7)
        assert (u.get_id("x"), u.get_id("y")) == (3, 7)
        assert (u.get_name(7), u.max_id(), len(u)) == ("y", 7, 2)
        assert u.suggest("") == ["x", "y"]
        with pytest.raises(NoSuchUniqueName):
            u.get_id("old")
        assert u.get_or_create_id("z") == 8

    def test_a_snapshot_load_moves_it(self, tmp_path):
        from opentsdb_tpu import TSDB, Config
        from opentsdb_tpu.core import persist
        conf = {"tsd.core.auto_create_metrics": "true",
                "tsd.tpu.warmup": "false"}
        first = TSDB(Config(**conf))
        first.add_point("m", 1356998400, 1, {"host": "web01"})
        persist.save_store(first, str(tmp_path))
        second = TSDB(Config(**conf))
        before = {kind: second.uids.by_kind(kind).generation
                  for kind in ("metric", "tagk", "tagv")}
        assert persist.load_store(second, str(tmp_path))
        assert second.uids.tag_values.get_name(1) == "web01"
        for kind, was in before.items():
            assert second.uids.by_kind(kind).generation > was
