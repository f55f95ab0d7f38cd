"""C++ decoder vs numpy reference: identical semantics, big speedup."""

import os
import time

import numpy as np
import pytest

from ccfd_tpu.native import (
    _decode_csv_numpy,
    decode_csv,
    native_available,
    pad_batch,
)


def make_csv(n_rows: int, n_features: int = 30, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    return (
        "\n".join(",".join(f"{v:.6f}" for v in row) for row in m) + "\n"
    ).encode()


def test_decode_roundtrip():
    data = make_csv(100)
    x, bad = decode_csv(data)
    assert x.shape == (100, 30) and bad == 0
    xr, badr = _decode_csv_numpy(data, 30)
    np.testing.assert_allclose(x, xr, rtol=1e-5, atol=1e-6)


def test_decode_bad_rows_zero_filled():
    data = b"1.0,2.0\nnot,a,row\n" + make_csv(1)
    x, bad = decode_csv(data)
    assert x.shape[0] == 3
    assert bad == 2
    assert np.all(x[0] == 0.0) and np.all(x[1] == 0.0)
    assert not np.all(x[2] == 0.0)


def test_decode_empty():
    x, bad = decode_csv(b"")
    assert x.shape == (0, 30) and bad == 0


def test_pad_batch_semantics():
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = pad_batch(x, 6)
    assert out.shape == (6, 3)
    np.testing.assert_array_equal(out[:4], x)
    assert np.all(out[4:] == 0)
    trunc = pad_batch(x, 2)
    np.testing.assert_array_equal(trunc, x[:2])


@pytest.mark.skipif(not native_available(), reason="no C++ toolchain")
def test_native_is_loaded_and_fast():
    data = make_csv(20000)
    t0 = time.perf_counter()
    x, _ = decode_csv(data)
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    xr, _ = _decode_csv_numpy(data, 30)
    t_py = time.perf_counter() - t0
    np.testing.assert_allclose(x, xr, rtol=1e-5, atol=1e-6)
    assert t_native < t_py  # the C++ path must actually win


def test_too_many_fields_rejected_both_paths():
    """Native and numpy decoders must agree: extra fields -> bad row."""
    data = b"1.0,2.0,3.0\n"
    for fn in (decode_csv, _decode_csv_numpy):
        x, bad = fn(data, 2)
        assert bad == 1, fn.__name__
        assert np.all(x[0] == 0.0), fn.__name__


def test_crlf_rows_ok_both_paths():
    data = b"1.0,2.0\r\n3.0,4.0\r\n"
    x, bad = decode_csv(data, 2)
    assert bad == 0
    np.testing.assert_allclose(x, [[1, 2], [3, 4]])


def test_decode_ndarray_json_canonical():
    from ccfd_tpu.native import decode_ndarray_json, native_available

    if not native_available():
        import pytest

        pytest.skip("no native toolchain")
    body = b'{"data": {"ndarray": [[1.0, 2.5, -3e2], [4, 5, 6]]}}'
    x = decode_ndarray_json(body, n_features=3)
    assert x is not None and x.shape == (2, 3)
    assert x[0].tolist() == [1.0, 2.5, -300.0]
    assert x[1].tolist() == [4.0, 5.0, 6.0]
    # short rows zero-pad to the schema (Python-path semantics)
    x = decode_ndarray_json(b'{"data":{"ndarray":[[7.0]]}}', n_features=3)
    assert x.tolist() == [[7.0, 0.0, 0.0]]
    # whitespace variants parse
    x = decode_ndarray_json(
        b'{ "data" : { "ndarray" : [ [ 1 , 2 ] , [ 3 , 4 ] ] } }', n_features=2
    )
    assert x.tolist() == [[1.0, 2.0], [3.0, 4.0]]
    # empty matrix is a valid zero-row decode
    x = decode_ndarray_json(b'{"data":{"ndarray":[]}}', n_features=3)
    assert x is not None and x.shape == (0, 3)


def test_decode_ndarray_json_bails_to_python_path():
    from ccfd_tpu.native import decode_ndarray_json, native_available

    if not native_available():
        import pytest

        pytest.skip("no native toolchain")
    nf = 3
    # a names key anywhere -> column remapping is the Python path's job
    assert decode_ndarray_json(
        b'{"data":{"names":["Amount"],"ndarray":[[1]]}}', nf
    ) is None
    # non-numeric cells, rows wider than the schema, malformed JSON, no key
    assert decode_ndarray_json(b'{"data":{"ndarray":[["x"]]}}', nf) is None
    assert decode_ndarray_json(b'{"data":{"ndarray":[[1,2,3,4]]}}', nf) is None
    assert decode_ndarray_json(b'{"data":{"ndarray":[[1,2', nf) is None
    assert decode_ndarray_json(b'{"data":{}}', nf) is None
    assert decode_ndarray_json(b"", nf) is None


def test_fast_server_http_contract():
    """FastHTTPServer speaks enough HTTP/1.1 for stdlib clients: keep-alive
    round trips, explicit close, 400 on garbage."""
    import http.client
    import json as _json

    from ccfd_tpu.utils.fasthttp import FastHTTPServer

    def handler(method, path, headers, body):
        if path == "/echo":
            return 200, "application/json", _json.dumps(
                {"method": method, "n": len(body)}
            ).encode()
        return 404, "text/plain", b"nope"

    srv = FastHTTPServer(("127.0.0.1", 0), handler).start()
    try:
        port = srv.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        for i in range(3):  # same connection: keep-alive works
            conn.request("POST", "/echo", b"x" * (10 + i))
            r = conn.getresponse()
            assert r.status == 200
            assert _json.loads(r.read()) == {"method": "POST", "n": 10 + i}
        conn.request("GET", "/missing", headers={"Connection": "close"})
        r = conn.getresponse()
        assert r.status == 404 and r.read() == b"nope"
        conn.close()
    finally:
        srv.stop()


def test_decode_ndarray_json_rejects_truncated_and_unwrapped():
    """Structurally invalid bodies must 400 via the Python path, not score
    natively (code-review r2 finding)."""
    from ccfd_tpu.native import decode_ndarray_json, native_available

    if not native_available():
        import pytest

        pytest.skip("no native toolchain")
    nf = 3
    # truncated after the matrix: invalid JSON
    assert decode_ndarray_json(b'{"data":{"ndarray":[[1,2,3]]', nf) is None
    assert decode_ndarray_json(b'{"data":{"ndarray":[[1,2,3]]}', nf) is None
    # no "data" wrapper: contract violation the JSON route 400s
    assert decode_ndarray_json(b'{"ndarray":[[1,2,3]]}', nf) is None
    # over-closed
    assert decode_ndarray_json(b'{"data":{"ndarray":[[1]]}}}', nf) is None
    # trailing keys after the matrix -> python path (it must still 200)
    assert decode_ndarray_json(
        b'{"data":{"ndarray":[[1,2,3]]},"meta":{"x":1}}', nf
    ) is None
    # but meta BEFORE data still decodes natively
    x = decode_ndarray_json(b'{"meta":{},"data":{"ndarray":[[1,2,3]]}}', nf)
    assert x is not None and x.tolist() == [[1.0, 2.0, 3.0]]


def test_fast_server_pipelined_and_split_requests():
    """Two requests arriving in one TCP segment, and a body split across
    segments, both parse correctly off the connection buffer."""
    import json as _json
    import socket
    import time

    from ccfd_tpu.utils.fasthttp import FastHTTPServer

    def handler(method, path, headers, body):
        return 200, "application/json", _json.dumps({"n": len(body)}).encode()

    srv = FastHTTPServer(("127.0.0.1", 0), handler).start()
    try:
        port = srv.server_address[1]
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        # two complete requests in ONE send
        req = b"POST /a HTTP/1.1\r\nContent-Length: 3\r\n\r\nabc"
        s.sendall(req + req)
        buf = b""
        deadline = time.time() + 5
        while buf.count(b'{"n": 3}') < 2 and time.time() < deadline:
            buf += s.recv(4096)
        assert buf.count(b'{"n": 3}') == 2, buf
        # body split across two sends (flush forced by a second sendall)
        s.sendall(b"POST /b HTTP/1.1\r\nContent-Length: 10\r\n\r\n12345")
        time.sleep(0.05)
        s.sendall(b"67890")
        buf = b""
        deadline = time.time() + 5  # fresh budget for this sub-case
        while b'{"n": 10}' not in buf and time.time() < deadline:
            chunk = s.recv(4096)
            if not chunk:
                break
            buf += chunk
        assert b'{"n": 10}' in buf
        s.close()
    finally:
        srv.stop()


def test_fast_server_rejects_oversize_head_and_bad_length():
    import socket
    import time

    from ccfd_tpu.utils.fasthttp import FastHTTPServer

    srv = FastHTTPServer(
        ("127.0.0.1", 0), lambda m, p, h, b: (200, "text/plain", b"ok")
    ).start()
    try:
        port = srv.server_address[1]
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(b"POST / HTTP/1.1\r\nContent-Length: zebra\r\n\r\n")
        buf = b""
        deadline = time.time() + 5
        while b"400" not in buf and time.time() < deadline:
            chunk = s.recv(4096)
            if not chunk:
                break
            buf += chunk
        assert b"400" in buf
        s.close()
        # oversize head: server answers 400 and closes instead of buffering
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(b"POST / HTTP/1.1\r\nX-Junk: " + b"a" * (70 * 1024))
        buf = b""
        deadline = time.time() + 5
        while b"400" not in buf and time.time() < deadline:
            chunk = s.recv(4096)
            if not chunk:
                break
            buf += chunk
        assert b"400" in buf
        s.close()
    finally:
        srv.stop()


def test_decode_ndarray_fuzz_never_crashes():
    """The C++ payload decoder parses attacker-controlled bytes in-process:
    mutations of valid payloads and random garbage must either decode or
    bail (None) — never corrupt memory or crash the interpreter."""
    import random

    from ccfd_tpu.native import decode_ndarray_json, native_available

    if not native_available():
        import pytest

        pytest.skip("no native toolchain")
    rng = random.Random(0)
    base = b'{"data": {"ndarray": [[1.5, -2.5, 3e10], [4, 5, 6]]}}'
    charset = b'[]{}",:.0123456789eE+-na '
    for trial in range(3000):
        b = bytearray(base)
        for _ in range(rng.randint(1, 6)):
            op = rng.random()
            pos = rng.randrange(len(b)) if b else 0
            if op < 0.4 and b:
                b[pos] = rng.choice(charset)
            elif op < 0.7 and b:
                del b[pos]
            else:
                b.insert(pos, rng.choice(charset))
        out = decode_ndarray_json(bytes(b), n_features=3)
        if out is not None:
            assert out.ndim == 2 and out.shape[1] == 3
            assert np.isfinite(out).all() or True  # nan/inf tolerated, no UB
    # pure garbage
    for trial in range(500):
        n = rng.randint(0, 200)
        junk = bytes(rng.randrange(256) for _ in range(n))
        out = decode_ndarray_json(junk, n_features=3)
        assert out is None or (out.ndim == 2 and out.shape[1] == 3)
    # pathological nesting / hugeness
    assert decode_ndarray_json(b'{"data":{"ndarray":' + b"[" * 10000, 3) is None
    deep = b'{"data":{"ndarray":[' + b"[1]," * 5000 + b"[1]]}}"
    out = decode_ndarray_json(deep, n_features=3)
    assert out is None or out.shape[0] == 5001


def test_decode_csv_fuzz_never_crashes():
    import random

    from ccfd_tpu.native import decode_csv, native_available

    if not native_available():
        import pytest

        pytest.skip("no native toolchain")
    rng = random.Random(1)
    for trial in range(1500):
        n = rng.randint(0, 300)
        junk = bytes(rng.randrange(256) for _ in range(n))
        x, bad = decode_csv(junk, n_features=30)
        assert x.shape[1] == 30 and bad >= 0


def _private_native_dir(tmp_path, monkeypatch):
    """Point the native module at a private copy of its sources so build
    tests never touch the package directory or the loaded library."""
    import shutil

    import ccfd_tpu.native as n

    pkg = tmp_path / "native"
    pkg.mkdir()
    for s in n._SRCS:
        shutil.copy(s, pkg / os.path.basename(s))
    srcs = [str(pkg / os.path.basename(s)) for s in n._SRCS]

    def fresh(srcs_override, so_path):
        monkeypatch.setattr(n, "_SRCS", srcs_override)
        monkeypatch.setattr(n, "_SO", so_path)
        monkeypatch.setattr(n, "_lib", None)
        monkeypatch.setattr(n, "_build_failed", False)

    return n, pkg, srcs, fresh


def test_native_degrades_never_hard_fails(tmp_path, monkeypatch):
    """The fallback contract across broken-artifact states: a corrupt
    .so under the expected name rebuilds from sources; stripped sources
    trust the shipped .so; nothing usable degrades to None (numpy paths)
    — no state raises."""
    import shutil

    n, pkg, srcs, fresh = _private_native_dir(tmp_path, monkeypatch)

    # NOTE: corrupt content goes into fresh files — overwriting a path a
    # previous CDLL still has mmap'd would corrupt the live mapping
    # (SIGBUS), which is a test artifact, not the contract under test.

    # corrupt .so at the digest name + sources present: rebuilt, loads
    fresh(srcs, str(pkg / "_ccfd_native.so"))
    with open(n._so_path(n._flags()), "wb") as f:
        f.write(b"not an elf")
    assert n._load() is not None

    # corrupt .so + sources stripped: degrade to None, not an exception
    so2 = str(pkg / "two_ccfd_native.so")
    with open(so2, "wb") as f:
        f.write(b"not an elf")
    fresh([str(pkg / "missing.cpp")], so2)
    assert n._load() is None

    # partial sources + a shipped .so: trusted (no FileNotFoundError)
    so3 = str(pkg / "three_ccfd_native.so")
    fresh(srcs, so3)
    shutil.copy(n._build(), so3)
    fresh([srcs[0], str(pkg / "missing.cpp")], so3)
    assert n._build() == so3
    assert n._load() is not None


def test_so_name_digest_decides_what_loads(tmp_path, monkeypatch):
    """While the sources are present the library is the one built from
    THEM, here: a .so left under the un-digested name (copied in from
    another machine, newer mtime and all) is not loaded, and editing a
    source or a flag changes the name, so the stale build is rebuilt, not
    reused."""
    n, pkg, srcs, fresh = _private_native_dir(tmp_path, monkeypatch)
    foreign = str(pkg / "_ccfd_native.so")
    with open(foreign, "wb") as f:
        f.write(b"machine code from somewhere else")
    os.utime(foreign, (2**31 - 1, 2**31 - 1))  # newer than every source
    fresh(srcs, foreign)
    built = n._build()
    assert built is not None and built != foreign
    assert built == n._so_path(n._flags()) and os.path.dirname(built) == str(pkg)
    assert n._load() is not None

    # an edited source: new digest, new build; the old file is not it
    os.utime(built, (2**31 - 1, 2**31 - 1))  # mtime must not matter
    with open(srcs[0], "a") as f:
        f.write("\n// edited\n")
    rebuilt = n._build()
    assert rebuilt not in (None, built, foreign) and os.path.exists(rebuilt)

    # the flags and (under -march=native) the host CPU are in the name too
    monkeypatch.setenv("CCFD_NATIVE_MARCH", "x86-64")
    portable = n._so_path(n._flags())
    assert portable != rebuilt
    monkeypatch.setattr(n, "_host_fingerprint", lambda: "another-cpu")
    assert n._so_path(n._flags()) == portable  # portable march: any host
    monkeypatch.delenv("CCFD_NATIVE_MARCH")
    assert n._so_path(n._flags()) != rebuilt   # native: this host only
