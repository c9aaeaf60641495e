"""Deep fuzz loop for the native wire codec under AddressSanitizer.

The in-CI fuzz pass (tests/test_fuzz_native.py) runs a bounded number of
hypothesis examples without instrumentation; this harness runs an
open-ended corpus-mutation loop against an ASAN build of _fastcodec, so
out-of-bounds reads/writes surface even when they don't crash.

Usage: ``python scripts/fuzz_native.py [seconds]`` (default 60).
Re-execs itself with libasan LD_PRELOADed (an ASAN .so cannot load into
an uninstrumented CPython otherwise), rebuilds the extension with
``GRAFT_NATIVE_ASAN=1`` into a scratch copy, and mutates a seed corpus
of valid payloads.  Any sanitizer report aborts the process — a clean
exit prints the iteration count.
"""
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time


def reexec_with_asan() -> None:
    if os.environ.get("GRAFT_FUZZ_CHILD"):
        return
    out = subprocess.run(["g++", "-print-file-name=libasan.so"],
                         capture_output=True, text=True, check=True)
    libasan = out.stdout.strip()
    env = dict(os.environ,
               GRAFT_FUZZ_CHILD="1",
               GRAFT_NATIVE_ASAN="1",
               LD_PRELOAD=libasan,
               # CPython leaks small arenas by design; leak detection
               # would drown real findings
               ASAN_OPTIONS="detect_leaks=0,abort_on_error=1",
               JAX_PLATFORMS="cpu")
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def main(budget_s: float) -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    # build the sanitized .so in a scratch copy so the regular build
    # (used by tests/bench) is untouched
    import crdt_graph_tpu.native as native
    scratch = tempfile.mkdtemp(prefix="fuzz_native_")
    shutil.copy(native._SRC, os.path.join(scratch, "fastcodec.cpp"))
    native._SRC = os.path.join(scratch, "fastcodec.cpp")
    mod = native.load(rebuild=True)
    if mod is None:
        print("build failed:", native._build_error)
        sys.exit(1)

    from crdt_graph_tpu.codec import json_codec, packed

    def pyside(payload):
        try:
            return True, packed.pack(json_codec.loads(payload))
        except (ValueError, RecursionError, OverflowError):
            return False, None

    seeds = [
        '{"op":"add","path":[0],"ts":1,"val":"a"}',
        '{"op":"del","path":[4294967297]}',
        '{"op":"batch","ops":[{"op":"add","path":[0],"ts":1,"val":'
        '{"k":[1,2.5,null,true,"\\ud800\\u00e9中"]}},'
        '{"op":"del","path":[1]},{"op":"future","x":[{"y":1}]}]}',
        '{"op":"add","path":[0,1,2,3,4,5,6,7],"ts":4611686018427387903,'
        '"val":[Infinity,-Infinity,NaN,1e308,-0.0,123456789012345678901]}',
    ]
    tokens = [b'{', b'}', b'[', b']', b'"', b':', b',', b'\\u0000',
              b'\\ud800', b'9' * 40, b'-', b'.', b'e999', b'null', b'true',
              b'Infinity', b'NaN', b'{"op":"batch","ops":[', b'\x00',
              b'\xf0\x9f\x98\x80', b'\xff', b' ', b'[' * 64]

    rng = random.Random(1234)
    deadline = time.monotonic() + budget_s
    n = accepted = 0
    while time.monotonic() < deadline:
        data = bytearray(rng.choice(seeds).encode())
        for _ in range(rng.randint(1, 12)):
            if not data:
                break
            i = rng.randrange(len(data))
            k = rng.randrange(6)
            if k == 0:
                data[i] ^= 1 << rng.randrange(8)
            elif k == 1:
                del data[i:i + rng.randint(1, 10)]
            elif k == 2:
                j = min(len(data), i + rng.randint(1, 16))
                data[i:i] = data[i:j]
            elif k == 3:
                data[i:i] = rng.choice(tokens)
            elif k == 4:
                data[i] = rng.randrange(256)
            else:
                del data[i:]
        n += 1
        payload = bytes(data)
        try:
            got = mod.parse_pack(payload, 16)
            native_ok = True
        except ValueError:
            native_ok = False
        except Exception as e:                     # noqa: BLE001
            print(f"NON-ValueError from parser: {type(e).__name__}: {e}")
            print("payload:", payload[:400])
            sys.exit(1)
        try:
            text = payload.decode()
        except UnicodeDecodeError:
            continue          # HTTP layer would have rejected upstream
        py_ok, _ = pyside(text)
        if native_ok != py_ok:
            print(f"ACCEPTANCE DIVERGED (native={native_ok}): {text[:400]!r}")
            sys.exit(1)
        accepted += native_ok
    print(f"fuzz clean: {n} iterations, {accepted} accepted, "
          f"{budget_s:.0f}s, ASAN silent")


if __name__ == "__main__":
    reexec_with_asan()
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 60.0)
