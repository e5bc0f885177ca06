"""Shared fixtures."""
import contextlib
import glob
import os

import pytest


class _Profile:
    """Records a block under ``jax.profiler.trace`` and reads back the
    host plane's events as ``(line, name, start_ns, end_ns, stats)``."""

    def __init__(self, path):
        self.path = str(path)

    @contextlib.contextmanager
    def trace(self):
        import jax
        with jax.profiler.trace(self.path):
            yield

    def host_events(self, prefixes=("",)):
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        pd = ProfileData.from_file(max(files, key=os.path.getmtime))
        out = []
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(tuple(prefixes)):
                        s = int(ev.start_ns)
                        out.append((ln.name, ev.name, s,
                                    s + int(ev.duration_ns),
                                    {k: v for k, v in ev.stats}))
        return sorted(out, key=lambda e: e[2])


@pytest.fixture
def profile(tmp_path):
    return _Profile(tmp_path / "profile")
