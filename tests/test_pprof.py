"""/pprof/profile and /pprof/heap emit the canonical pprof protobuf wire
format (reference builtin/pprof_service.cpp parity): validated here by
parsing the bytes with protobuf proper against a dynamically-built
profile.proto descriptor (the image has no `go` toolchain; `go tool
pprof` consumes exactly what this descriptor describes).
"""

import threading
import urllib.request

import pytest


def _profile_descriptor_cls(name):
    pb = pytest.importorskip("google.protobuf")
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    fdp = descriptor_pb2.FileDescriptorProto(
        name="pprof_profile_test.proto", package="pp", syntax="proto3")
    vt = fdp.message_type.add(name="ValueType")
    vt.field.add(name="type", number=1, type=3, label=1)   # int64
    vt.field.add(name="unit", number=2, type=3, label=1)
    sm = fdp.message_type.add(name="Sample")
    sm.field.add(name="location_id", number=1, type=4, label=3)  # uint64
    sm.field.add(name="value", number=2, type=3, label=3)
    ln = fdp.message_type.add(name="Line")
    ln.field.add(name="function_id", number=1, type=4, label=1)
    loc = fdp.message_type.add(name="Location")
    loc.field.add(name="id", number=1, type=4, label=1)
    f = loc.field.add(name="line", number=4, type=11, label=3)
    f.type_name = ".pp.Line"
    fn = fdp.message_type.add(name="Function")
    fn.field.add(name="id", number=1, type=4, label=1)
    fn.field.add(name="name", number=2, type=3, label=1)
    fn.field.add(name="system_name", number=3, type=3, label=1)
    pr = fdp.message_type.add(name="Profile")
    f = pr.field.add(name="sample_type", number=1, type=11, label=3)
    f.type_name = ".pp.ValueType"
    f = pr.field.add(name="sample", number=2, type=11, label=3)
    f.type_name = ".pp.Sample"
    f = pr.field.add(name="location", number=4, type=11, label=3)
    f.type_name = ".pp.Location"
    f = pr.field.add(name="function", number=5, type=11, label=3)
    f.type_name = ".pp.Function"
    pr.field.add(name="string_table", number=6, type=9, label=3)
    pr.field.add(name="duration_nanos", number=10, type=3, label=1)
    f = pr.field.add(name="period_type", number=11, type=11, label=1)
    f.type_name = ".pp.ValueType"
    pr.field.add(name="period", number=12, type=3, label=1)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"pp.{name}"))


@pytest.fixture(scope="module")
def busy_server():
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.runtime import native

    server = native.Server()
    server.add_echo_service()
    port = server.start("127.0.0.1:0")
    # Load generator: the CPU sampler only sees threads that burn cpu.
    stop = threading.Event()

    def burn():
        ch = native.Channel(f"127.0.0.1:{port}", timeout_ms=5000)
        # Large payloads: every message allocates fresh IOBuf blocks, so
        # the heap sampler sees steady allocation traffic too.
        payload = b"x" * (512 * 1024)
        while not stop.is_set():
            ch.call("EchoService/Echo", b"m", payload)

    threads = [threading.Thread(target=burn, daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    yield port
    stop.set()
    for t in threads:
        t.join(timeout=5)
    server.stop()


def _check_profile(raw, expect_samples, n_value_types=2):
    Profile = _profile_descriptor_cls("Profile")
    prof = Profile.FromString(raw)
    # Spec invariants go tool pprof relies on:
    assert prof.string_table and prof.string_table[0] == ""
    assert len(prof.sample_type) == n_value_types
    for vt in prof.sample_type:
        assert 0 < vt.type < len(prof.string_table)
        assert 0 < vt.unit < len(prof.string_table)
    assert prof.period > 0
    functions = {f.id for f in prof.function}
    locations = {l.id for l in prof.location}
    for loc in prof.location:
        for line in loc.line:
            assert line.function_id in functions
    for s in prof.sample:
        assert len(s.value) == len(prof.sample_type)
        for lid in s.location_id:
            assert lid in locations
    for f in prof.function:
        assert 0 < f.name < len(prof.string_table)
    if expect_samples:
        assert len(prof.sample) > 0
        # Symbolized frames, not raw addresses.
        names = [prof.string_table[f.name] for f in prof.function]
        assert any(len(n) > 3 for n in names)
    return prof


def test_pprof_profile_wire_format(busy_server):
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{busy_server}/pprof/profile?seconds=2",
        timeout=30).read()
    prof = _check_profile(raw, expect_samples=True)
    assert prof.duration_nanos == 2_000_000_000


def test_pprof_heap_wire_format(busy_server):
    # Heap samples depend on allocation traffic landing INSIDE the 1s
    # sampling window; the echo load allocates steadily (IOBuf blocks),
    # but on a 2-core box host steal can starve the burner threads for a
    # whole window (observed once across a full run — PR 6 notes), so a
    # dry window gets a bounded rerun instead of failing tier-1. The
    # wire-format invariants are asserted on EVERY attempt; only the
    # has-samples expectation reruns.
    raw = b""
    for _attempt in range(3):
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{busy_server}/pprof/heap?seconds=1",
            timeout=30).read()
        prof = _check_profile(raw, expect_samples=False, n_value_types=1)
        if len(prof.sample) > 0:
            break
    # Byte-valued profiles carry ONE value type (inuse_space/bytes) — a
    # (samples, count) column would mislabel byte counts.
    _check_profile(raw, expect_samples=True, n_value_types=1)


def test_contention_page_format_under_induced_contention(busy_server):
    """/contention?seconds=N renders the FiberMutex wait profile. A debug
    hook hammers one FiberMutex from many fibers THROUGH the profile
    window (the page's own start/stop wraps the sampling), so the report
    must show at least one contended stack with wait totals and
    symbolized frames — mirroring the /hotspots and /heap coverage."""
    import re
    import threading

    from brpc_tpu.runtime import native

    # Contenders run past the 2s profile window; the ctypes call blocks a
    # plain Python thread (GIL released), not the profile request below.
    gen = threading.Thread(
        target=lambda: native.lib().tbrpc_debug_induce_contention(8, 4000),
        daemon=True)
    gen.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{busy_server}/contention?seconds=2",
            timeout=30).read().decode()
    finally:
        gen.join(timeout=10)
    # Header line: "<N> contended stack(s); <M> sample(s) kept, ..."
    m = re.match(r"^(\d+) contended stack\(s\); (\d+) sample\(s\) kept",
                 body.splitlines()[0])
    assert m, f"unexpected /contention header: {body.splitlines()[0]!r}"
    assert int(m.group(1)) > 0, body
    # Every stack block reports its total wait (us, or ns under a
    # microsecond) and hit count...
    waits = re.findall(r"-- waited (\d+)(?:us|ns) total over (\d+) hit\(s\):",
                       body)
    assert waits and all(int(w) > 0 and int(h) > 0 for w, h in waits), body
    # ...and symbolized frames (dladdr resolves exported symbols; the
    # anonymous-namespace contender itself renders as a raw address, but
    # the fiber entry above it must symbolize).
    assert re.search(r"_Z\w+", body), body[:2000]


def test_fibers_page_shows_parked_fiber_stack(busy_server):
    """/fibers lists live fibers and walks parked fibers' saved stacks. A
    Python service handler sleeping on the callback pool parks its service
    fiber in a butex wait, so the page must show a parked fiber whose
    symbolized frames reach the butex layer."""
    import threading
    import time

    from brpc_tpu.runtime import native

    release = threading.Event()

    def slow_handler(method, request, att):
        release.wait(15)
        return b"done", b""

    server = native.Server()
    server.add_service("SlowSvc", slow_handler)
    port = server.start("127.0.0.1:0")
    ch = native.Channel(f"127.0.0.1:{port}", timeout_ms=30000)
    caller = threading.Thread(
        target=lambda: ch.call("SlowSvc/Poke", b"m", b""), daemon=True)
    caller.start()
    try:
        deadline = time.monotonic() + 10
        while True:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fibers", timeout=10).read().decode()
            parked = [blk for blk in body.split("fiber ")
                      if blk.startswith(tuple("0123456789abcdef"))
                      and "parked" in blk.splitlines()[0]]
            # The service fiber parked on the handler's CountdownEvent has
            # a walkable stack: butex_wait at (or near) the innermost frame.
            if any("butex_wait" in blk for blk in parked):
                break
            assert time.monotonic() < deadline, \
                f"no parked fiber with a butex_wait stack:\n{body}"
            time.sleep(0.2)
        first_line = body.splitlines()[0]
        assert "live fiber(s)" in first_line
    finally:
        release.set()
        caller.join(timeout=10)
        ch.close()
        server.close()
