"""End-to-end LD_PRELOAD test of the C joystick interposer.

A subprocess runs with the interposer preloaded and opens /dev/input/js0;
the shim redirects it to our GamepadServer unix socket, consumes the config
blob, emulates the joystick ioctls, and streams js_event packets
(reference counterpart: addons/js-interposer/js-interposer-test.py).
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys

import pytest

from selkies_tpu.input_host.gamepad import GamepadServer

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
SO_PATH = os.path.join(NATIVE_DIR, "selkies_joystick_interposer.so")
SRC_PATH = os.path.join(NATIVE_DIR, "joystick_interposer.c")

if not os.path.exists(SO_PATH):  # build artifacts are not committed
    subprocess.run(["make", "-C", NATIVE_DIR, "-s", "selkies_joystick_interposer.so"],
                   check=False, capture_output=True, timeout=120)


def _loadable(path: str) -> bool:
    """Probe the .so in a THROWAWAY process (an interposer dlopen'd into
    pytest would hook libc calls here): a prebuilt artifact from a newer
    glibc fails the loader on older images."""
    probe = subprocess.run(
        [sys.executable, "-c", f"import ctypes; ctypes.CDLL({path!r})"],
        capture_output=True, timeout=60)
    return probe.returncode == 0


def _usable_so_path(tmpdir: str) -> str:
    """The committed .so when this loader accepts it; otherwise rebuild
    from source into tmpdir (skip if no compiler)."""
    if _loadable(SO_PATH):
        return SO_PATH
    import shutil as _shutil

    cc = _shutil.which("cc") or _shutil.which("gcc")
    if cc is None:
        pytest.skip("prebuilt interposer incompatible with this glibc "
                    "and no C compiler to rebuild")
    out = os.path.join(tmpdir, "selkies_joystick_interposer.so")
    r = subprocess.run([cc, "-O2", "-Wall", "-fPIC", "-shared", "-o", out,
                        SRC_PATH, "-ldl"],
                       capture_output=True, text=True, timeout=120)
    if r.returncode != 0 or not _loadable(out):
        pytest.skip(f"interposer rebuild failed: {r.stderr[:300]}")
    return out

CLIENT_SCRIPT = r"""
import fcntl, os, struct, sys

fd = os.open("/dev/input/js0", os.O_RDONLY)

# JSIOCGAXES / JSIOCGBUTTONS / JSIOCGVERSION / JSIOCGNAME
buf = bytearray(1)
fcntl.ioctl(fd, 0x80016a11, buf)  # JSIOCGAXES
axes = buf[0]
buf = bytearray(1)
fcntl.ioctl(fd, 0x80016a12, buf)  # JSIOCGBUTTONS
btns = buf[0]
buf = bytearray(4)
fcntl.ioctl(fd, 0x80046a01, buf)  # JSIOCGVERSION
version = struct.unpack("I", buf)[0]
name = bytearray(128)
n = fcntl.ioctl(fd, (2 << 30) | (ord('j') << 8) | 0x13 | (128 << 16), name)  # JSIOCGNAME(128)
name = name.rstrip(b"\x00").decode()
btnmap = bytearray(btns * 2)
fcntl.ioctl(fd, (2 << 30) | (ord('j') << 8) | 0x34 | (len(btnmap) << 16), btnmap)
first_btn = struct.unpack_from("H", btnmap, 0)[0]

print(f"CONFIG axes={axes} btns={btns} version={version:#x} name={name} first_btn={first_btn:#x}", flush=True)

# read the neutral burst + one live event
total = btns + axes + 1
events = []
for _ in range(total):
    data = os.read(fd, 8)
    while len(data) < 8:
        data += os.read(fd, 8 - len(data))
    events.append(struct.unpack("IhBB", data))
last = events[-1]
print(f"EVENT value={last[1]} type={last[2]} number={last[3]}", flush=True)
os.close(fd)
"""


@pytest.mark.skipif(not os.path.exists(SO_PATH), reason="interposer not built")
def test_interposer_end_to_end(tmp_path):
    so_path = _usable_so_path(str(tmp_path))

    async def scenario():
        js = GamepadServer(str(tmp_path / "selkies_js0.sock"))
        await js.start()

        env = dict(os.environ)
        env["LD_PRELOAD"] = so_path
        env["SELKIES_INTERPOSER_SOCKET_PATH"] = str(tmp_path)
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-c", CLIENT_SCRIPT,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        )

        # give the client time to connect + receive config + neutral burst,
        # then send the live event it waits for
        await asyncio.sleep(1.5)
        js.send_btn(0, 1)

        out, err = await asyncio.wait_for(proc.communicate(), 20)
        text = out.decode()
        assert proc.returncode == 0, f"client failed: {err.decode()}\n{text}"
        assert "CONFIG axes=8 btns=11" in text
        assert "name=Selkies Controller" in text
        assert "first_btn=0x130" in text  # BTN_A
        assert "EVENT value=1 type=1 number=0" in text
        await js.stop()

    asyncio.run(scenario())
