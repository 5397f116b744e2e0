"""coll/quant — block-scale quantized collectives and KV slabs.

Covers the acceptance list of ISSUE 15: codec round-trip units (block
boundaries, scale edge cases, cross-process determinism), the
(dtype, size, accuracy_budget) ladder (quant only under an EXPLICIT
budget, never for non-commutative ops, force-vars win), the device
tier (budget-armed comm routes to the pallas encode/dequant-accumulate
programs), the wire tier (>=2x fewer bytes at 4MB over loopback tcp
with the tolerance check passing; corrupt quant frames fail as loudly
as crc32 ones, chaos-armed), the serving KV tier (decode within band,
codec change -> stale hints fall back to full prefill), the tolerance
harness itself, the CPU AOT compile of the codec kernels (the
re-earnable device contract), otpu_info --quant, and the committed
bench-row pins.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import traceback
import zlib
from pathlib import Path

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api import op as op_mod
from ompi_tpu.mca.coll import quant

from launch import tpurun

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    if w.size != 8:
        pytest.skip("needs 8 virtual devices")
    yield w
    rt.reset_for_testing()


def spmd(comm, fn, timeout=60):
    """One thread per rank over the in-process world (the
    test_coll_algorithms harness)."""
    size = comm.size
    results = [None] * size
    errors = []

    def run(i):
        try:
            results[i] = fn(comm.as_rank(i), i)
        except Exception:
            errors.append((i, traceback.format_exc()))

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(size)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not errors, errors[0]
    assert not any(t.is_alive() for t in threads), "spmd rank hung"
    return results


def _mp_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    return env


# ----------------------------------------------------------- codec units

def test_codec_roundtrip_bands_and_boundaries():
    rng = np.random.default_rng(0)
    for n in (1, 5, 127, 128, 129, 257, 1000, 4096):
        x = (rng.standard_normal(n) * 10).astype(np.float32)
        for codec in quant.CODECS:
            enc = quant.encode_f32(x, codec, 128)
            assert enc.dtype == np.uint8
            assert enc.nbytes == quant.encoded_nbytes(n, codec, 128)
            dec = quant.decode_f32(enc, codec, n, 128)
            rel = np.abs(dec - x).max() / np.abs(x).max()
            assert rel <= quant.CODEC_BANDS[codec] + 1e-9, \
                (n, codec, rel)
    # the capacity a fixed byte budget gains: 3.88x for int8 (one f32
    # scale a block of 128), 2.0x for bf16, at 4,096 elements
    x = np.ones(4096, np.float32)
    assert quant.encode_f32(x, "int8", 128).nbytes == 4224
    assert quant.encode_f32(x, "bf16", 128).nbytes == 8192


def test_codec_scale_edge_cases():
    # all-zero block: scale 0, exact zeros back
    z = np.zeros(300, np.float32)
    assert np.array_equal(
        quant.decode_f32(quant.encode_f32(z, "int8"), "int8", 300), z)
    # huge magnitudes (near f32 max) and denormal-scale tinies survive
    for fill in (3e38, 1e-30, -2.5e7):
        x = np.full(257, fill, np.float32)
        d = quant.decode_f32(quant.encode_f32(x, "int8"), "int8", 257)
        np.testing.assert_allclose(d, x, rtol=0.01)
    # mixed-magnitude block: the small element's error is bounded by
    # the BLOCK max (the block-scale contract), not its own magnitude
    x = np.array([1e6] + [1.0] * 127, np.float32)
    d = quant.decode_f32(quant.encode_f32(x, "int8", 128), "int8",
                         128, 128)
    assert abs(d[1] - 1.0) <= 0.5 * 1e6 / 127 + 1e-3
    # NaN payloads SURVIVE the bf16 truncation (the naive rounding add
    # carries into the exponent and flushes payload NaNs to +/-0.0 —
    # silently defeating overflow detection), and infinities hold
    pats = np.array([0x7FFFFFFF, 0xFFFFFFFF, 0x7FFF8000, 0x7FC00000,
                     0x7F800000, 0xFF800000], np.uint32)
    d = quant.decode_f32(quant.encode_f32(pats.view(np.float32),
                                          "bf16"), "bf16", pats.size)
    assert np.isnan(d[:4]).all(), d
    assert np.isposinf(d[4]) and np.isneginf(d[5])
    # a truncated payload is a loud error, never a silent misparse
    enc = quant.encode_f32(np.ones(256, np.float32), "int8")
    with pytest.raises(ValueError, match="does not match"):
        quant.decode_f32(enc[:-1], "int8", 256)


def test_codec_cross_process_determinism(tmp_path):
    """Identical input encodes to identical bytes in a fresh process
    with randomized hashing — the property the KV prefix cache and the
    wire receive parse rely on."""
    body = (
        "import numpy as np, zlib\n"
        "from ompi_tpu.mca.coll import quant\n"
        "x = np.random.default_rng(42).standard_normal(5000)"
        ".astype(np.float32)\n"
        "print(zlib.crc32(quant.encode_f32(x, 'int8', 128).tobytes()),"
        " zlib.crc32(quant.encode_f32(x, 'bf16').tobytes()))\n")
    x = np.random.default_rng(42).standard_normal(5000).astype(
        np.float32)
    here = (zlib.crc32(quant.encode_f32(x, "int8", 128).tobytes()),
            zlib.crc32(quant.encode_f32(x, "bf16").tobytes()))
    env = dict(_mp_env(), PYTHONHASHSEED="random")
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    got = tuple(int(v) for v in out.stdout.split())
    assert got == here, "codec bytes differ across processes"


# ------------------------------------------------------- decision ladder

def test_decide_rule_key():
    f32, big = np.float32, 1 << 20
    assert quant.decide("allreduce", f32, big, 0.01) == "int8"
    assert quant.decide("allreduce", f32, big,
                        quant.CODEC_BANDS["int8"]) == "int8"
    assert quant.decide("allreduce", f32, big, 0.005) == "bf16"
    assert quant.decide("allreduce", f32, big, 0.001) is None
    assert quant.decide("allreduce", f32, big, None) is None
    assert quant.decide("allreduce", f32, big, 0.0) is None
    # exact dtypes and non-f32 floats are excluded
    assert quant.decide("allreduce", np.int32, big, 0.01) is None
    assert quant.decide("allreduce", np.float64, big, 0.01) is None
    # non-commutative reductions are excluded (the tuned gate)
    assert quant.decide("allreduce", f32, big, 0.01,
                        commute=False) is None
    # below the size floor the encode never earns its cost
    assert quant.decide("allreduce", f32, 1024, 0.01) is None
    # only the implemented collectives
    assert quant.decide("bcast", f32, big, 0.01) is None
    assert quant.decide("allgather", f32, big, 0.01) == "int8"


def test_budget_info_key_parsing(world, capsys):
    c = world.dup()
    assert quant.budget_of(c) is None
    c.info.set("otpu_quant_budget", "0.01")
    assert quant.budget_of(c) == 0.01
    assert quant.pick(c, "allreduce", np.float32, 1 << 20,
                      op_mod.SUM) == "int8"
    # malformed budget: loud show_help, quant stays OFF
    c.info.set("otpu_quant_budget", "not-a-float")
    assert quant.budget_of(c) is None
    assert "does not parse" in capsys.readouterr().err


# ---------------------------------------------------- tuned (host) tier

def _rank_data(n, elems, seed):
    return np.stack([np.random.default_rng([seed, r])
                     .standard_normal(elems)
                     for r in range(n)]).astype(np.float32)


@pytest.fixture()
def tuned_module(world):
    from ompi_tpu.base import mca
    from ompi_tpu.mca.coll.tuned import TunedModule

    fw = mca.framework("coll")
    fw.open()
    comp = fw.components["tuned"]
    return TunedModule(comp), comp


def test_tuned_quant_only_under_budget(world, tuned_module):
    from ompi_tpu.runtime import spc

    mod, _ = tuned_module
    spc.init()
    data = _rank_data(8, 64 * 1024, seed=21)   # 256KB f32
    exact = data.astype(np.float64).sum(0)

    # no budget: the exact ladder path, zero codec activity
    enc0 = spc.read("quant_encodes")
    out = spmd(world, lambda c, r: mod.allreduce(c, data[r]))
    assert np.abs(out[0] - exact).max() / np.abs(exact).max() < 1e-5
    assert spc.read("quant_encodes") == enc0, \
        "quantized WITHOUT an accuracy budget"

    world.info.set("otpu_quant_budget", "0.02")
    try:
        out = spmd(world, lambda c, r: mod.allreduce(c, data[r]))
        rel = np.abs(out[0] - exact).max() / np.abs(exact).max()
        assert 1e-7 < rel <= quant.CODEC_BANDS["int8"] * 1.2, rel
        assert spc.read("quant_encodes") > enc0
        # every rank folds in rank order: results bit-identical
        for r in range(1, 8):
            assert np.array_equal(out[0], out[r])
        # allgather arm: each block decodes within band at every rank
        g = spmd(world, lambda c, r: mod.allgather(c, data[r][:32768]))
        relg = np.abs(g[0] - data[:, :32768]).max() / np.abs(data).max()
        assert 1e-7 < relg <= quant.CODEC_BANDS["int8"]
    finally:
        world.info.delete("otpu_quant_budget")


def test_tuned_quant_never_noncommutative(world, tuned_module):
    from ompi_tpu.runtime import spc

    mod, _ = tuned_module
    spc.init()

    def first_half(invec, inoutvec, datatype=None):
        half = len(inoutvec) // 2
        inoutvec[:half] = invec[:half]
        inoutvec[half:] += invec[half:]

    ncop = op_mod.create(first_half, commute=False)
    data = _rank_data(8, 64 * 1024, seed=22)
    world.info.set("otpu_quant_budget", "0.02")
    try:
        enc0 = spc.read("quant_encodes")
        out = spmd(world, lambda c, r: mod.allreduce(c, data[r], ncop))
        assert spc.read("quant_encodes") == enc0, \
            "non-commutative op was quantized"
        # order-safe fold: acc = data[r] (op) acc, r descending (the
        # recursive-doubling grouping differs associatively, so a few
        # f32 ulps of slack — far below any codec band)
        exact = data[7].copy()
        for r in range(6, -1, -1):
            exact = ncop.reduce_arrays(data[r], exact)
        np.testing.assert_allclose(out[0], exact, rtol=1e-4, atol=1e-5)
    finally:
        world.info.delete("otpu_quant_budget")


def test_tuned_force_var_beats_quant(world, tuned_module,
                                     fresh_registry):
    from ompi_tpu.runtime import spc

    mod, _ = tuned_module
    spc.init()
    fresh_registry.set("otpu_coll_tuned_allreduce_algorithm", "ring")
    data = _rank_data(8, 64 * 1024, seed=23)
    world.info.set("otpu_quant_budget", "0.02")
    try:
        enc0 = spc.read("quant_encodes")
        out = spmd(world, lambda c, r: mod.allreduce(c, data[r]))
        assert spc.read("quant_encodes") == enc0, \
            "force-var override was quantized away"
        exact = data.astype(np.float64).sum(0)
        assert np.abs(out[0] - exact).max() / np.abs(exact).max() < 1e-5
    finally:
        world.info.delete("otpu_quant_budget")


def test_tolerance_harness_on_tuned_quant(world, tuned_module):
    """The dryrun tolerance-band check driving the REAL quant ladder
    path (the satellite: run_tolerance_check used in tier-1 quant
    tests) — and its loud failure names the (coll, size, dtype) cell."""
    from ompi_tpu.parallel.dryrun import run_tolerance_check

    mod, _ = tuned_module
    world.info.set("otpu_quant_budget", "0.02")
    try:
        def approx(stack):
            out = spmd(world,
                       lambda c, r: mod.allreduce(c, stack[r]))
            return out[0]

        report = run_tolerance_check(
            "allreduce_quant", approx, nranks=8,
            sizes=(32 * 1024,), band=quant.CODEC_BANDS["int8"])
        assert report["allreduce_quant/32768/float32"] > 1e-7
    finally:
        world.info.delete("otpu_quant_budget")
    # the loud path: an impossible band names the failing cell
    with pytest.raises(RuntimeError) as ei:
        run_tolerance_check(
            "quant_rt",
            lambda stack: quant.decode_f32(
                quant.encode_f32(stack.sum(0), "int8"), "int8",
                stack.shape[1]),
            sizes=(2048,), band=1e-9)
    assert "(quant_rt, 2048, float32)" in str(ei.value)


# --------------------------------------------------------- device tier

def test_device_quant_allreduce_and_allgather(world):
    xla = next(m for m in world.coll_modules
               if type(m).__name__ == "XlaCollModule")
    host = _rank_data(8, 65536, seed=31)
    exact = host.astype(np.float64).sum(0)

    # no budget: bit-exact-grade device path
    dev = xla.make_world_array(host)
    out = np.asarray(world.allreduce_array(dev))
    assert np.abs(out - exact).max() / np.abs(exact).max() < 1e-5

    q = world.dup()
    q.info.set("otpu_quant_budget", "0.02")
    xla_q = next(m for m in q.coll_modules
                 if type(m).__name__ == "XlaCollModule")
    dev_q = xla_q.make_world_array(host)
    out_q = np.asarray(q.allreduce_array(dev_q))
    rel = np.abs(out_q - exact).max() / np.abs(exact).max()
    assert 1e-7 < rel <= quant.CODEC_BANDS["int8"] * 1.2, rel
    # compiled program cache: the second call is the same program
    assert np.array_equal(out_q, np.asarray(q.allreduce_array(dev_q)))
    # quant allgather decodes within the single-encode band
    ag = np.asarray(q.allgather_array(dev_q))
    relg = np.abs(ag - host).max() / np.abs(host).max()
    assert 1e-7 < relg <= 0.5 / 127 * 1.5, relg
    # MAX is not a psum reduction: it must take the exact path
    mx = np.asarray(q.allreduce_array(dev_q, op_mod.MAX))
    np.testing.assert_allclose(mx, host.max(0), rtol=1e-6)


def test_quant_kernels_aot_compile_cpu():
    """The codec kernels must COMPILE under JAX_PLATFORMS=cpu AOT in
    interpret mode (the real Mosaic gate rides tools/pallas_aot.py's
    quant_* cases)."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops import pallas_quant as pq

    rows = (1 << 16) // pq.LANES
    for fn, args in (
            (pq.encode_int8,
             (jax.ShapeDtypeStruct((rows, pq.LANES), jnp.float32),)),
            (pq.dequant_accumulate,
             (jax.ShapeDtypeStruct((8, rows, pq.LANES), jnp.int8),
              jax.ShapeDtypeStruct((8, rows, 1), jnp.float32))),
            (pq.decode_int8,
             (jax.ShapeDtypeStruct((rows, pq.LANES), jnp.int8),
              jax.ShapeDtypeStruct((rows, 1), jnp.float32)))):
        compiled = fn.lower(*args, interpret=True).compile()
        assert compiled is not None


# ----------------------------------------------------------- wire tier

def _mk_conn():
    from ompi_tpu.mca.btl import tcp as tcp_mod

    s1, s2 = socket.socketpair()
    conn = tcp_mod._Conn(s1)
    conn.rank = 9
    return tcp_mod, conn, (s1, s2)


def _quant_frame(tcp_mod, x: np.ndarray, cksum: bool = True):
    """A quantized fast-header frame, built the way send() builds it."""
    from ompi_tpu.mca.btl.base import MATCH, Frag

    payload = memoryview(x).cast("B")
    enc = quant.encode_wire(payload, "int8")
    qhdr = tcp_mod._QHDR.pack(quant.codec_id("int8"), len(payload),
                              quant.block_elems())
    hdr = tcp_mod._fast_header(Frag(0, 9, 0, 5, 1, MATCH,
                                    b"x" * len(payload)))
    htype = tcp_mod._H_FAST | tcp_mod._H_QUANT
    if cksum:
        crc = zlib.crc32(memoryview(enc),
                         zlib.crc32(hdr, zlib.crc32(qhdr)))
        frame_len = (1 + tcp_mod._CKSUM.size + len(qhdr) + len(hdr)
                     + enc.nbytes)
        return bytearray(
            tcp_mod._LEN.pack(frame_len)
            + bytes((htype | tcp_mod._H_CK_BASE,))
            + tcp_mod._CKSUM.pack(crc) + qhdr + hdr + enc.tobytes())
    frame_len = 1 + len(qhdr) + len(hdr) + enc.nbytes
    return bytearray(tcp_mod._LEN.pack(frame_len) + bytes((htype,))
                     + qhdr + hdr + enc.tobytes())


def test_wire_quant_frame_roundtrip():
    tcp_mod, conn, socks = _mk_conn()
    btl = tcp_mod.TcpBtl()
    got = []
    btl.set_recv_callback(got.append)
    try:
        x = np.random.default_rng(3).standard_normal(16384).astype(
            np.float32)
        n = btl._on_bytes(conn, memoryview(_quant_frame(tcp_mod, x)))
        assert n == 1
        dec = np.frombuffer(bytes(got[0].data), np.float32)
        # the parse decodes EXACTLY what the codec encodes...
        ref = quant.decode_f32(quant.encode_f32(x, "int8"), "int8",
                               x.size)
        assert np.array_equal(dec, ref)
        # ...and lands within the codec band of the original
        assert np.abs(dec - x).max() / np.abs(x).max() <= 0.5 / 127 + 1e-9
        assert not got[0].borrowed   # decoded payload owns its memory
    finally:
        for s in socks:
            s.close()


def test_wire_quant_frames_fail_as_loudly_as_crc(capsys):
    """Corrupt quant frames: crc-armed bit rot AND a garbage quant
    sub-header both die with an attributed SanitizeError + show_help —
    never a silently-wrong delivery."""
    from ompi_tpu.base import output
    from ompi_tpu.runtime import sanitizer, spc

    spc.init()
    output._help_seen.clear()   # show_help dedups per key in a window
    tcp_mod, conn, socks = _mk_conn()
    btl = tcp_mod.TcpBtl()
    btl.set_recv_callback(lambda frag: None)
    try:
        x = np.ones(4096, np.float32)
        frame = _quant_frame(tcp_mod, x, cksum=True)
        frame[-3] ^= 0x20                 # wire bit rot under crc
        before = spc.read("wire_cksum_fail")
        with pytest.raises(sanitizer.SanitizeError):
            btl._on_bytes(conn, memoryview(frame))
        assert spc.read("wire_cksum_fail") == before + 1
        assert "corrupted on the wire" in capsys.readouterr().err
        # unchecksummed frame whose quant header lies about its length:
        # the decode length check catches it loudly
        frame2 = _quant_frame(tcp_mod, x, cksum=False)
        tcp_mod._QHDR.pack_into(frame2, tcp_mod._LEN.size + 1,
                                quant.codec_id("int8"),
                                4096 * 4 + 64, quant.block_elems())
        with pytest.raises(sanitizer.SanitizeError) as ei:
            btl._on_bytes(conn, memoryview(frame2))
        assert "rank 9" in str(ei.value)
        assert "does not decode" in capsys.readouterr().err
    finally:
        for s in socks:
            s.close()


_WIRE_JOB = """
import json
import numpy as np
import ompi_tpu
from ompi_tpu.mca.coll import quant
from ompi_tpu.runtime import spc

w = ompi_tpu.init()
n = (4 << 20) // 4
base = np.stack([np.random.default_rng([7, r]).standard_normal(n)
                 for r in range(w.size)]).astype(np.float32)
exact = base.astype(np.float64).sum(0)
got = np.asarray(w.allreduce(base[w.rank]))
rel = float(np.max(np.abs(got - exact)) / np.max(np.abs(exact)))
st = quant.wire_stats()
print("WIRE%d " % w.rank + json.dumps(
    {"orig": st["orig"], "enc": st["enc"],
     "saved": spc.read("quant_wire_bytes_saved"), "rel": rel}),
    flush=True)
ompi_tpu.finalize()
"""


def test_wire_4MB_moves_at_least_2x_fewer_bytes(tmp_path):
    """THE wire acceptance: a 4MB f32 host allreduce over loopback tcp
    with quantize-on-pack armed moves >=2x fewer payload bytes (int8
    block codec measures ~3.9x) and the result stays inside the codec
    band."""
    script = tmp_path / "wire_job.py"
    script.write_text(_WIRE_JOB)
    proc = tpurun(
        2, script, timeout=240,
        extra=("--fake-nodes", "2",
               "--mca", "otpu_coll_sm_coll_priority", "0",
               "--mca", "otpu_coll_quant_wire", "1",
               "--mca", "otpu_coll_tuned_allreduce_algorithm",
               "recursive_doubling",
               "--mca", "pml_ob1_stripe", "0",
               "--mca", "pml_ob1_rget_limit", "0"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    reps = [json.loads(ln.split(" ", 2)[2])
            for ln in proc.stdout.splitlines() if "WIRE" in ln]
    assert len(reps) == 2, proc.stdout
    for rep in reps:
        # each rank pushed its 4MB contribution through the codec
        assert rep["orig"] >= 4 << 20
        assert rep["enc"] * 2 <= rep["orig"], \
            f"only {rep['orig'] / max(1, rep['enc']):.2f}x fewer bytes"
        assert rep["saved"] == rep["orig"] - rep["enc"]
        # tolerance check: within the int8 accumulate band
        assert 1e-7 < rep["rel"] <= quant.CODEC_BANDS["int8"], rep


_CHAOS_JOB = """
import numpy as np
import ompi_tpu
from ompi_tpu.ft import chaos

w = ompi_tpu.init()
x = np.ones((256 << 10) // 4, np.float32)
for it in range(4):
    if chaos.enabled:
        chaos.kill_point("step", it)
    got = np.asarray(w.allreduce(x))
    assert np.allclose(got, w.size, atol=0.1), "silently wrong result"
print("CHAOS-QUANT-OK rank %d" % w.rank, flush=True)
ompi_tpu.finalize()
"""


def _run_chaos_quant_job(tmp_path, spec):
    script = tmp_path / "chaos_job.py"
    script.write_text(_CHAOS_JOB)
    return tpurun(
        2, script, timeout=150,
        extra=("--fake-nodes", "2",
               "--mca", "otpu_coll_sm_coll_priority", "0",
               "--mca", "otpu_coll_quant_wire", "1",
               "--mca", "otpu_coll_quant_min_bytes", "4k",
               "--mca", "otpu_chaos_spec", spec,
               "--mca", "otpu_chaos_seed", "3",
               "--mca", "ft_detector", "true",
               "--mca", "ft_detector_period", "0.3",
               "--mca", "ft_detector_timeout", "6.0",
               "--mca", "ft_detector_startup_grace", "6.0"))


def test_chaos_corrupt_quant_frames_loud(tmp_path):
    """Chaos-armed wire corruption with quant frames on the wire: the
    armed crc (chaos arms checksumming) catches every flip LOUDLY —
    completion-or-attributed-error, never silent wrong data (the
    worker itself checks every result)."""
    r = _run_chaos_quant_job(tmp_path, "corrupt:p=1")
    out = r.stdout + r.stderr
    assert r.returncode != 0, "every frame corrupted yet the job passed?"
    assert ("corrupted on the wire" in out or "crc32" in out
            or "does not decode" in out), out[-3000:]


def test_chaos_kill_with_quant_wire_no_hang(tmp_path):
    """Chaos kill mid-run with the quant wire armed: the survivor
    fails loudly (detector -> ProcFailed) inside the timeout — a codec
    stage must not turn a peer death into a hang."""
    r = _run_chaos_quant_job(tmp_path, "kill:rank=1,step=2")
    out = r.stdout + r.stderr
    assert r.returncode != 0, out[-2000:]
    assert ("chaos" in out or "failed" in out.lower()), out[-3000:]


# ------------------------------------------------------- serving KV tier

def test_kv_quant_slab_e2e(world):
    """Quantized KV slabs over the partitioned persistent pairing:
    blocks land within the codec band, the epoch machinery is
    untouched, and the capacity multiplier is the users-per-chip win."""
    from ompi_tpu.runtime.progress import progress
    from ompi_tpu.serving.kv_stream import KvSlabReceiver, KvSlabSender
    from ompi_tpu.serving.worker import toy_kv

    a, b = world.as_rank(0), world.as_rank(1)
    snd = KvSlabSender(a, peer=1, slots=4, elems_per_slot=256, tag=93,
                       codec="int8")
    rcv = KvSlabReceiver(b, peer=0, slots=4, elems_per_slot=256,
                         tag=93, partitions=8, codec="int8")
    assert snd.capacity_multiplier >= 2.0
    assert rcv.slab.nbytes * 2 <= 4 * 256 * 4  # 2-4x more slots/byte
    band = quant.CODEC_BANDS["int8"]
    try:
        for epoch in range(3):
            snd.begin_epoch(epoch)
            rcv.begin_epoch(epoch)
            kv = toy_kv(epoch * 10 + 2, 256)
            snd.write_slot(2, kv)
            snd.slot_ready(2)
            for _ in range(400):
                if rcv.slot_arrived(2):
                    break
                progress()
            assert rcv.slot_arrived(2), "readied slot never arrived"
            got = rcv.read_slot(2)
            tol = band * max(1e-6, float(np.abs(kv).max()))
            assert np.allclose(got, kv, atol=tol, rtol=0.0)
            assert not np.array_equal(got, kv) or kv.max() == 0
            snd.finish_epoch(wait=True)
            rcv.finish_epoch()
    finally:
        snd.free()
        rcv.free()


def test_kv_decode_worker_verifies_within_band(world):
    """A decode-stage worker with a quantized receiver accepts the
    in-band block and stores it as its decode state."""
    from ompi_tpu.runtime.progress import progress
    from ompi_tpu.serving.kv_stream import KvSlabSender
    from ompi_tpu.serving.worker import ShardWorker, toy_kv

    a, b = world.as_rank(2), world.as_rank(3)
    wk = ShardWorker(b, router=2, role="decode", peer=2, slots=4,
                     kv_elems=256, kv_codec="int8")
    snd = KvSlabSender(a, peer=3, slots=4, elems_per_slot=256,
                       tag=7001, codec="int8")
    # point the worker's receiver at OUR sender pairing (same tag)
    wk._receiver.free()
    from ompi_tpu.serving.kv_stream import KvSlabReceiver

    wk._receiver = KvSlabReceiver(b, peer=2, slots=4,
                                  elems_per_slot=256, tag=7001,
                                  codec="int8")
    try:
        snd.begin_epoch(0)
        snd.write_slot(1, toy_kv(77, 256))
        snd.slot_ready(1)
        snd.finish_epoch(wait=True)
        # _on_kv IS the verify path under test: begin, poll, band-check
        # (raises on an out-of-band block), store, reply
        wk._on_kv(0, [(77, 1)])
        expect = toy_kv(77, 256)
        tol = quant.CODEC_BANDS["int8"] * float(np.abs(expect).max())
        assert np.allclose(wk._kv[77], expect, atol=tol, rtol=0.0)
        # drain the worker's reply so the module world stays clean
        kind, epoch, rids = a.recv_obj(3, 602)   # worker.TAG_RES
        assert (kind, epoch, rids) == ("kv_ready", 0, [77])
    finally:
        snd.free()
        wk._receiver.free()


def test_kv_codec_change_is_stale_generation():
    """A codec change bumps the PrefixStore generation: every hint
    minted against the old encoding falls back to FULL PREFILL — a
    perf miss, never wrong KV (the stale-hint guarantee surviving a
    codec change)."""
    from ompi_tpu.runtime import spc
    from ompi_tpu.serving.prefix_cache import PrefixStore, block_hashes
    from ompi_tpu.serving.worker import ShardWorker, toy_kv

    spc.init()
    wk = ShardWorker.__new__(ShardWorker)
    wk.kv_elems = 16
    wk._prefix = PrefixStore(capacity=8)
    wk._prefix.set_codec("")
    wk._prefix_hits = 0
    wk._preport_installed, wk._preport_evicted = [], []
    wk._preport_prefills = 0
    ch = block_hashes(list(range(8)), 4)
    prefills0 = spc.read("serve_prefills")
    wk._prefill_or_skip(11, 8, ch, None)
    gen0 = wk._prefix.generation
    # verified hint at the raw-codec generation: prefill skipped
    wk._prefill_or_skip(12, 8, ch, (ch[1], gen0, 2))
    assert spc.read("serve_prefills") == prefills0 + 1
    # the codec flips (reconfiguration): generation bumps
    wk._prefix.set_codec("int8")
    assert wk._prefix.generation == gen0 + 1
    stale0 = spc.read("serve_prefix_stale")
    kv = wk._prefill_or_skip(13, 8, ch, (ch[1], gen0, 2))
    np.testing.assert_array_equal(kv, toy_kv(13, 16))   # never wrong KV
    assert spc.read("serve_prefills") == prefills0 + 2, \
        "stale hint did not fall back to full prefill"
    assert spc.read("serve_prefix_stale") == stale0 + 1
    # idempotent re-set does NOT churn the generation
    g = wk._prefix.generation
    wk._prefix.set_codec("int8")
    assert wk._prefix.generation == g


# --------------------------------------------------- surfaces and pins

def test_otpu_info_quant(capsys):
    from ompi_tpu.tools.otpu_info import main

    assert main(["--quant", "--parsable"]) == 0
    out = capsys.readouterr().out
    assert "quant budget info key:otpu_quant_budget" in out
    assert "quant var otpu_coll_quant_block" in out
    assert "quant var otpu_coll_quant_wire" in out
    assert "quant var otpu_coll_quant_kv_codec" in out
    assert "quant stage quant.encode" in out
    assert "quant counter quant_wire_bytes_saved" in out


def test_wire_disabled_is_identity_off():
    """Module-bool identity: with the var at its default the pml/btl
    codec stage is one bool check — no Frag carries a codec stamp."""
    from ompi_tpu.base.var import registry

    var = registry.lookup("otpu_coll_quant_wire")
    assert var is not None and not bool(var.value)
    assert quant.wire_enabled is False
