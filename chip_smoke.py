"""chip_smoke.py — does the system still start on the chip?

Drives the compute plane's main paths once, through the entry points a
user calls, at the full width of the models the README names:

- ``kernels``: every Pallas entry in ``skypilot_tpu/ops`` compiled with
  ``interpret=False`` at the 8B geometry and compared with the jnp
  reference of its own module.
- ``serve``: ``python -m skypilot_tpu.infer.server --model 8b --quantize
  --paged --slots 16 --max-seq-len 2048`` (random int8 weights from a
  seed), registered READY in ``serve/state`` and fronted by the real
  load balancer; a short prompt, a ~600-token prompt, four at once and
  a repeated greedy prompt, streamed through the LB; then ``/metrics``
  and ``/health``.
- ``train``: ``python -m skypilot_tpu.train.run --model llama-350m
  --steps 6 --batch 8 --seq 2048`` — Pallas flash forward and backward,
  loss finite and falling.
- with four or more chips also ``train4`` (``--fsdp 2 --tp 2``),
  ``serve4`` (``--model 8b --tp 4``, bf16, dense cache) and ``graft``
  (``python __graft_entry__.py 4``: ring attention, GPipe, ep=4), each
  checked for placement: every device holds shards and memory.

One process owns the chip at a time. This parent never imports jax or
the package: each phase is a child, waited on until it has exited
before the next starts, and the load balancer is a CPU-only child.
Children log to the output directory (``chiprun_out/chip_smoke/``); a
failed phase prints its log's tail and the run exits non-zero. Without
a TPU the probe fails the run in seconds; ``--rehearse-cpu`` is the
explicit tiny CPU rehearsal of the same phases (interpret-mode kernels,
``tiny`` models), never a fallback.

The last line of a full passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as the probe child's jax reported it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
TOKENIZER = os.path.join(ROOT, 'examples', 'tokenizer_8k.json')
CACHE_ENV = 'JAX_COMPILATION_CACHE_DIR'

# Real sizes: the README / examples/serve_llm.yaml one-chip serving
# configuration and the largest train/run.py preset that holds Adam
# state on one v5e chip. 'kernels' is the 8B attention geometry.
REAL = {
    'platform': 'tpu',
    'kernels': dict(hkv=8, group=4, hd=128, page=64, chunk=256, slots=16,
                    max_pages=32, verify_r=7, flash_seq=2048,
                    flash_heads=((32, 8, 128), (16, 8, 64)),
                    ce=(1024, 4096, 128256)),
    'serve': ['--model', '8b', '--quantize', '--paged', '--slots', '16',
              '--max-seq-len', '2048', '--tokenizer', TOKENIZER],
    'serve_tp': ['--model', '8b', '--tp', '4', '--slots', '16',
                 '--max-seq-len', '2048', '--tokenizer', TOKENIZER],
    'tp': 4,
    'long_prompt': [(i * 7919) % 8000 + 1 for i in range(600)],
    'train': ['--model', 'llama-350m', '--steps', '6', '--batch', '8',
              '--seq', '2048', '--log-every', '1'],
    'attention': 'flash',
}
# The explicit CPU rehearsal: same phases, tiny models, interpreted
# kernels. tp=2 because the tiny model has two KV heads.
REHEARSAL = {
    'platform': 'cpu',
    'kernels': dict(hkv=2, group=2, hd=32, page=16, chunk=32, slots=4,
                    max_pages=8, verify_r=4, flash_seq=256,
                    flash_heads=((4, 2, 32),), ce=(64, 64, 1024)),
    'serve': ['--model', 'tiny', '--paged', '--page-size', '16',
              '--slots', '4', '--max-seq-len', '128'],
    'serve_tp': ['--model', 'tiny', '--tp', '2', '--slots', '4',
                 '--max-seq-len', '128'],
    'tp': 2,
    'long_prompt': [(i * 31) % 200 + 1 for i in range(70)],
    'train': ['--model', 'llama-tiny', '--steps', '6', '--batch', '8',
              '--seq', '64', '--log-every', '1'],
    'attention': 'dense',
}
ONE_CHIP_PHASES = ('kernels', 'serve', 'train')
FOUR_CHIP_PHASES = ('train4', 'serve4', 'graft')
KERNEL_TOLERANCE = 2e-2     # max|out-ref| / max|ref|; bf16 / int8 inputs


class PhaseFailed(Exception):
    """A phase did not meet its checks; the message says which."""


# What a failed phase raises: its own verdict, a refused connection or
# HTTP error, or an answer / log line that does not parse.
PHASE_ERRORS = (PhaseFailed, OSError, ValueError, KeyError)


# ---------------------------------------------------------------------------
# Children (each runs in its own process; these import jax / the package)
# ---------------------------------------------------------------------------
def _child_probe() -> int:
    """What jax finds, with the environment as this run was given it."""
    import jax

    from skypilot_tpu.utils import jax_env
    cache = jax_env.attach_compile_cache()
    info = jax_env.device_summary()
    info['compile_cache_dir'] = cache
    info['jax'] = jax.__version__
    info['env'] = {k: v for k, v in sorted(os.environ.items())
                   if k.startswith(('JAX_', 'XLA_', 'TPU_', 'LIBTPU_'))}
    # jax.devices() order is what parallel/mesh.py reshapes into a
    # mesh: record it beside each chip's torus coordinates.
    info['device_order'] = [
        {'id': d.id, 'coords': list(getattr(d, 'coords', ()) or ()),
         'process': d.process_index} for d in jax.devices()]
    print(json.dumps(info), flush=True)
    return 0


def _child_lb(service: str, port: int, replica_url: str) -> int:
    """Register ``replica_url`` as a READY replica of ``service`` in
    serve/state (under this run's SKY_TPU_HOME) and run the real load
    balancer in front of it — what the serve controller does for a
    replica that passed its readiness probe."""
    from skypilot_tpu.serve import load_balancer
    from skypilot_tpu.serve import state as serve_state
    serve_state.add_service(service, spec_json='{}', task_yaml='',
                            lb_port=port, lb_policy='least_load')
    rid = serve_state.add_replica(service, 'chip-smoke', 1)
    serve_state.set_replica_url(rid, replica_url)
    serve_state.set_replica_status(rid, serve_state.ReplicaStatus.READY)
    load_balancer.run_load_balancer(service, 'least_load', '127.0.0.1',
                                    port)
    return 0


def _child_kernels(rehearse: bool, out_path: str) -> int:
    """Compile and run every Pallas entry against its reference.
    ``interpret`` is passed explicitly — False on the chip — so a wrong
    backend cannot downgrade a kernel to the interpreter."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.ops import attention as att
    from skypilot_tpu.ops import cross_entropy as ce
    from skypilot_tpu.ops import paged_attention as pa
    from skypilot_tpu.utils import jax_env

    cache = jax_env.attach_compile_cache()
    device = jax_env.device_summary()
    interpret = rehearse
    g = (REHEARSAL if rehearse else REAL)['kernels']
    hkv, group, hd = g['hkv'], g['group'], g['hd']
    page, slots, maxp = g['page'], g['slots'], g['max_pages']
    rng = np.random.default_rng(0)

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def reference(fn, *args, **kw):
        # TPU matmuls default to one bf16 pass; the reference must not.
        with jax.default_matmul_precision('highest'):
            return fn(*args, **kw)

    @functools.lru_cache(maxsize=None)     # one pool per page dtype
    def paged(int8: bool):
        # Page 0 and seven spares that no table names: the int8 pool's
        # scale rows then pair up into whole 128-lane rows, as served.
        n_pages = slots * maxp + 8
        k = normal((hkv, n_pages, page, hd), jnp.float32)
        v = normal((hkv, n_pages, page, hd), jnp.float32)
        if int8:
            (k, ks), (v, vs) = pa.quantize_rows(k), pa.quantize_rows(v)
            scales = dict(k_scales=ks, v_scales=vs)
        else:
            k, v, scales = k.astype(jnp.bfloat16), v.astype(
                jnp.bfloat16), {}
        tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))[
            :slots * maxp].reshape(slots, maxp), jnp.int32)
        top = maxp * page - g['verify_r']
        lengths = rng.integers(1, top + 1, size=slots)
        lengths[:3] = (1, page, top)       # edges: first row, page end, full
        return k, v, tables, jnp.asarray(lengths, jnp.int32), scales

    def decode(int8: bool, impl: str):
        k, v, tables, lengths, sc = paged(int8)
        q = normal((slots, hkv, group, hd))
        if impl != 'jax':       # a slot that is not decoding: length 0
            lengths = lengths.at[3].set(0)
        live = np.asarray(lengths) > 0
        out = pa.paged_decode_attention(q, k, v, tables, lengths,
                                        interpret=interpret, impl=impl,
                                        **sc)
        ref = reference(pa.paged_decode_attention_reference, q, k, v,
                        tables, lengths, **sc)
        pairs = [(out[live], ref[live])]
        if not live.all():      # what the kernel left alone: zeros
            pairs.append((out[~live], jnp.zeros_like(out[~live])))
        return pairs

    def prefill(int8: bool):
        k, v, tables, _, sc = paged(int8)
        chunk = g['chunk']
        q = normal((chunk, hkv, group, hd))
        # Page-aligned but not chunk-aligned offset (a prefix-cache
        # match boundary), a ragged final chunk.
        offset, true_len = 5 * page, chunk - 7
        out = pa.paged_prefill_attention(
            q, k, v, tables[0], jnp.int32(offset), jnp.int32(true_len),
            interpret=interpret, **sc)
        ref = reference(pa.paged_prefill_attention_reference, q, k, v,
                        tables[0], offset, true_len, **sc)
        return [(out[:true_len], ref[:true_len])]    # pad rows: garbage

    def verify(int8: bool):
        k, v, tables, lengths, sc = paged(int8)
        q = normal((slots, g['verify_r'], hkv, group, hd))
        out = pa.paged_verify_attention(q, k, v, tables, lengths,
                                        interpret=interpret, **sc)
        ref = reference(pa.paged_verify_attention_reference, q, k, v,
                        tables, lengths, **sc)
        return [(out, ref)]

    def flash(hq: int, hkv_: int, d: int):
        s = g['flash_seq']
        q, k, v = (normal((1, h, s, d)) for h in (hq, hkv_, hkv_))
        w = normal((1, hq, s, d), jnp.float32)

        def loss(fn, q_, k_, v_):
            out = fn(q_, k_, v_)
            return jnp.sum(out.astype(jnp.float32) * w), out

        kernel = lambda q_, k_, v_: att.flash_attention(  # noqa: E731
            q_, k_, v_, causal=True, interpret=interpret)
        dense = lambda q_, k_, v_: att.dense_attention(  # noqa: E731
            q_, k_, v_, causal=True)
        grad = lambda fn: jax.jit(jax.grad(  # noqa: E731
            lambda *a: loss(fn, *a), argnums=(0, 1, 2), has_aux=True))
        got_g, got = grad(kernel)(q, k, v)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        want_g, want = reference(grad(dense), *f32)
        return [(got, want), *zip(got_g, want_g)]

    def fused_ce():
        t, d, vocab = g['ce']
        x = normal((t, d))
        w = normal((d, vocab), scale=d ** -0.5)
        targets = jnp.asarray(rng.integers(0, vocab, size=t), jnp.int32)
        out = ce.fused_cross_entropy(x, w, targets, interpret=interpret)

        def dense(x_, w_):
            logp = jax.nn.log_softmax(
                x_.astype(jnp.float32) @ w_.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(logp, targets[:, None],
                                        axis=1)[:, 0]
        return [(out, reference(dense, x, w))]

    entries: List[Tuple[str, Callable[[], list]]] = [
        (f'flash_fwd_bwd_hd{d}', lambda a=(hq, hk, d): flash(*a))
        for hq, hk, d in g['flash_heads']]
    entries += [
        # impl='auto', what the step programs call: this repo's kernel.
        ('paged_decode_bf16', lambda: decode(False, 'auto')),
        ('paged_decode_int8', lambda: decode(True, 'auto')),
        # jax's library kernel, while impl='jax' exists (ROADMAP D7).
        ('paged_decode_library_bf16', lambda: decode(False, 'jax')),
        ('paged_prefill_bf16', lambda: prefill(False)),
        ('paged_prefill_int8', lambda: prefill(True)),
        ('paged_verify_bf16', lambda: verify(False)),
        ('paged_verify_int8', lambda: verify(True)),
        ('fused_cross_entropy_fwd', fused_ce),
    ]
    results = []
    for name, fn in entries:
        t0 = time.time()
        row: Dict[str, Any] = {'kernel': name}
        try:
            pairs = fn()
            err = 0.0
            for out, ref in pairs:
                out = np.asarray(out, np.float32)
                ref = np.asarray(ref, np.float32)
                if out.shape != ref.shape or not np.isfinite(out).all():
                    err = math.inf
                    break
                err = max(err, float(np.abs(out - ref).max()
                                     / max(np.abs(ref).max(), 1e-6)))
            row['rel_err'] = err
            row['status'] = ('ok' if err <= KERNEL_TOLERANCE
                             else 'mismatch')
        except Exception as e:  # noqa: BLE001 — the compiler's refusal IS the finding
            row['status'] = 'error'
            row['error'] = f'{type(e).__name__}: {e}'[:4000]
        row['seconds'] = round(time.time() - t0, 2)
        results.append(row)
        print(json.dumps(row), flush=True)
    with open(out_path, 'w', encoding='utf-8') as f:
        json.dump({'device': device, 'compile_cache_dir': cache,
                   'interpret': interpret, 'kernels': results}, f,
                  indent=1)
    return 0 if all(r['status'] == 'ok' for r in results) else 1


# ---------------------------------------------------------------------------
# Parent: process handling
# ---------------------------------------------------------------------------
class Runner:
    """Starts children, logs them, and stops every one it started."""

    def __init__(self, out_dir: str, rehearse: bool) -> None:
        self.out_dir = out_dir
        self.rehearse = rehearse
        self.sizes = REHEARSAL if rehearse else REAL
        # The compile cache the probe child reported; every later
        # chip-owning child must report the same one.
        self.cache_dir: Optional[str] = None
        self._procs: List[subprocess.Popen] = []

    def check_cache_dir(self, reported: Optional[str]) -> None:
        """The cache in force must be the one the environment placed
        or else one fixed path inside the checkout — the same for every
        process, never unset."""
        placed = os.environ.get(CACHE_ENV)
        if self.cache_dir is None:
            inside = (reported or '').startswith(ROOT + os.sep)
            if not (reported == placed if placed else inside):
                raise PhaseFailed(
                    f'compile cache in force is {reported!r}; expected '
                    f'{placed or "a path inside " + ROOT!r}')
            self.cache_dir = reported
        elif reported != self.cache_dir:
            raise PhaseFailed(f'compile cache in force is {reported!r}, '
                              f'the probe reported {self.cache_dir!r}')

    def env(self, cpu_only: bool = False) -> Dict[str, str]:
        env = dict(os.environ)
        prior = env.get('PYTHONPATH', '')
        env['PYTHONPATH'] = (f'{ROOT}{os.pathsep}{prior}' if prior
                             else ROOT)
        env['SKY_TPU_HOME'] = os.path.join(self.out_dir, 'home')
        if cpu_only or self.rehearse:
            env['JAX_PLATFORMS'] = 'cpu'
        if self.rehearse:
            flags = re.sub(r'--xla_force_host_platform_device_count=\d+',
                           '', env.get('XLA_FLAGS', ''))
            env['XLA_FLAGS'] = (
                f'{flags} --xla_force_host_platform_device_count=4'
            ).strip()
        return env

    def log_path(self, name: str) -> str:
        return os.path.join(self.out_dir, f'{name}.log')

    def start(self, name: str, cmd: List[str],
              cpu_only: bool = False) -> subprocess.Popen:
        with open(self.log_path(name), 'wb') as log:
            proc = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, cwd=ROOT,
                env=self.env(cpu_only), start_new_session=True)
        self._procs.append(proc)
        return proc

    def run(self, name: str, cmd: List[str], timeout: float) -> None:
        """Run a child to its end; non-zero or overtime fails the
        phase."""
        proc = self.start(name, cmd)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise PhaseFailed(f'{name}: no exit after {timeout:.0f}s')
        if rc != 0:
            raise PhaseFailed(f'{name}: exit code {rc}')

    def stop(self, proc: subprocess.Popen) -> None:
        """SIGTERM the child's process group, SIGKILL after 15 s, and
        return only once it has exited (the next phase needs the
        chip)."""
        if proc.poll() is None:
            for sig, wait_s in ((signal.SIGTERM, 15), (signal.SIGKILL, 15)):
                try:
                    os.killpg(proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    proc.wait(timeout=wait_s)
                    break
                except subprocess.TimeoutExpired:
                    continue
        if proc in self._procs:
            self._procs.remove(proc)

    def stop_all(self) -> None:
        for proc in list(self._procs):
            self.stop(proc)

    def tail(self, name: str, lines: int = 60) -> str:
        try:
            with open(self.log_path(name), encoding='utf-8',
                      errors='replace') as f:
                return ''.join(f.readlines()[-lines:])
        except OSError as e:
            return f'(no log: {e})'

    def child(self, *args: str) -> List[str]:
        return [sys.executable, os.path.abspath(__file__), '--child',
                *args]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _get_json(url: str, timeout: float = 10.0) -> Tuple[int, Any]:
    """(status, body) of a GET; 5xx bodies are read too (/health
    answers 503 with the reason)."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            return e.code, json.loads(body)
        except ValueError:
            return e.code, {'raw': body.decode('utf-8', 'replace')}


def _generate(url: str, payload: Dict[str, Any],
              timeout: float = 600.0) -> Dict[str, Any]:
    """One streamed /generate: returns {'tokens', 'done', 'seconds'}."""
    req = urllib.request.Request(
        url, data=json.dumps({**payload, 'stream': True}).encode(),
        headers={'Content-Type': 'application/json'})
    t0 = time.time()
    tokens: List[int] = []
    done: Dict[str, Any] = {}
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            msg = json.loads(line)
            if 'error' in msg:
                raise PhaseFailed(f'/generate streamed an error: '
                                  f'{msg["error"]}')
            tokens += msg.get('tokens', [])
            if msg.get('done'):
                done = msg
    if not done:
        raise PhaseFailed('/generate stream ended without a done line')
    return {'tokens': tokens, 'done': done,
            'seconds': round(time.time() - t0, 2)}


# ---------------------------------------------------------------------------
# Parent: phases
# ---------------------------------------------------------------------------
def phase_probe(r: Runner) -> Dict[str, Any]:
    r.run('probe', r.child('probe'), timeout=300)
    info = json.loads(r.tail('probe', 1))
    if info['platform'] != r.sizes['platform']:
        raise PhaseFailed(
            f'jax found platform {info["platform"]!r} '
            f'({info["device_kind"]} x{info["count"]}), this run needs '
            f'{r.sizes["platform"]!r}')
    r.check_cache_dir(info['compile_cache_dir'])
    return info


def phase_kernels(r: Runner) -> str:
    out_path = os.path.join(r.out_dir, 'kernels.json')
    if os.path.exists(out_path):
        os.remove(out_path)
    t0 = time.time()
    cmd = r.child('kernels', out_path)
    if r.rehearse:
        cmd.append('--rehearse-cpu')
    try:
        r.run('kernels', cmd, timeout=900)
    finally:
        report = None
        if os.path.exists(out_path):
            with open(out_path, encoding='utf-8') as f:
                report = json.load(f)
            for row in report['kernels']:
                print(f'[smoke] kernels: {row["kernel"]} {row["status"]} '
                      f'{row["seconds"]}s rel_err='
                      f'{row.get("rel_err", "-")}'
                      + (f'\n{row["error"]}' if 'error' in row else ''),
                      flush=True)
    if report is None:
        raise PhaseFailed('kernels: the child wrote no report')
    if report['device']['platform'] != r.sizes['platform']:
        raise PhaseFailed(f'kernels ran on {report["device"]}')
    if report['interpret'] != r.rehearse:
        raise PhaseFailed('kernels ran with the wrong interpret mode')
    r.check_cache_dir(report['compile_cache_dir'])
    return (f'{len(report["kernels"])} Pallas entries compiled '
            f'(interpret={report["interpret"]}) and matched in '
            f'{time.time() - t0:.0f}s')


def _wait_healthy(r: Runner, name: str, proc: subprocess.Popen,
                  url: str, timeout: float) -> float:
    """Poll /health until ok. A dead engine loop (503 'dead': e.g. a
    Mosaic compile error during warm-up) or an exited process fails at
    once with the reason instead of waiting out the timeout."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        if proc.poll() is not None:
            raise PhaseFailed(f'{name}: server exited with code '
                              f'{proc.returncode} before it was healthy')
        try:
            status, body = _get_json(f'{url}/health', timeout=5)
        except (urllib.error.URLError, OSError, ValueError):
            time.sleep(1.0)
            continue
        if body.get('status') == 'ok':
            return time.time() - t0
        if body.get('status') in ('dead', 'corrupt'):
            raise PhaseFailed(f'{name}: /health says {body}')
        time.sleep(1.0)
    raise PhaseFailed(f'{name}: not healthy after {timeout:.0f}s')


def _serve(r: Runner, name: str, server_args: List[str],
           tp: int) -> str:
    home = os.path.join(r.out_dir, 'home')
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(home)
    port, lb_port = _free_port(), _free_port()
    url, lb_url = f'http://127.0.0.1:{port}', f'http://127.0.0.1:{lb_port}'
    server = r.start(name, [
        sys.executable, '-m', 'skypilot_tpu.infer.server', '--host',
        '127.0.0.1', '--port', str(port), *server_args])
    lb = None
    try:
        ready_s = _wait_healthy(r, name, server, url, timeout=900)
        lb = r.start(f'{name}_lb', r.child('lb', f'smoke-{name}',
                                           str(lb_port), url),
                     cpu_only=True)
        t0 = time.time()
        while True:
            if lb.poll() is not None:
                raise PhaseFailed(
                    f'{name}: load balancer exited ({lb.returncode})\n'
                    + r.tail(f'{name}_lb'))
            try:
                if _get_json(f'{lb_url}/-/metrics',
                             timeout=5)[1].get('ready_replicas'):
                    break
            except (urllib.error.URLError, OSError, ValueError):
                pass
            if time.time() - t0 > 60:
                raise PhaseFailed(f'{name}: LB saw no ready replica in '
                                  f'60s\n' + r.tail(f'{name}_lb'))
            time.sleep(0.5)

        gen = f'{lb_url}/generate'
        short = {'prompt': 'hello world', 'max_new_tokens': 8}
        first = _generate(gen, short)
        long_ = _generate(gen, {'tokens': r.sizes['long_prompt'],
                                'max_new_tokens': 8})
        batch: List[Any] = [None] * 4

        def one(i: int) -> None:
            try:
                batch[i] = _generate(gen, {
                    'prompt': f'request number {i}: hello',
                    'max_new_tokens': 16})
            except Exception as e:  # noqa: BLE001 — re-raised below, on the main thread
                batch[i] = e
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        for b in batch:
            if not isinstance(b, dict):
                raise PhaseFailed(f'{name}: concurrent request failed: '
                                  f'{b!r}')
        again = _generate(gen, short)

        answers = [(first, 8), (long_, 8), *((b, 16) for b in batch),
                   (again, 8)]
        for ans, want in answers:
            if (len(ans['tokens']) != want
                    or ans['done'].get('finish_reason') != 'max_tokens'
                    or not all(isinstance(t, int) and t >= 0
                               for t in ans['tokens'])):
                raise PhaseFailed(f'{name}: bad answer {ans}')
        if again['tokens'] != first['tokens']:
            raise PhaseFailed(
                f'{name}: repeated greedy prompt diverged: '
                f'{first["tokens"]} then {again["tokens"]}')

        _, m = _get_json(f'{url}/metrics')
        _, lbm = _get_json(f'{lb_url}/-/metrics')
        status, health = _get_json(f'{url}/health')
        emitted = sum(want for _, want in answers)
        checks = {
            'device is the expected platform':
                m['device']['platform'] == r.sizes['platform']
                and m['device']['count'] >= tp,
            'decode tokens counted':
                m['decode_tokens'] >= emitted - len(answers),
            'no preemption': m.get('preemptions', 0) == 0,
            'programs compiled and counted':
                m['compiled_programs'].get('decode') == 1
                and m['compiled_programs'].get('prefill', 0) >= 1
                and min(m['compiled_programs'].values()) >= 0,
            'integrity ok': m['integrity'] == 'ok',
            'health ok': status == 200 and health.get('status') == 'ok',
            'LB forwarded every request':
                lbm.get('requests_failed') == 0
                and lbm.get('requests_total', 0) >= len(answers),
        }
        r.check_cache_dir(m['compile_cache_dir'])
        mem = m['device_memory_bytes'][:tp]
        if tp > 1 and all(b is not None for b in mem):
            checks['every device holds memory, none holds it all'] = (
                min(mem) > 0 and max(mem) < 2 * min(mem))
        bad = [k for k, ok in checks.items() if not ok]
        if bad:
            raise PhaseFailed(f'{name}: {bad}; /metrics={m}')
        log = r.tail(name, 10_000)
        stamp = {k: (m_.group(1) if m_ else '?') for k, m_ in (
            ('weights_s', re.search(r'weights ready in ([\d.]+)s', log)),
            ('warm_s', re.search(r'engine warm in ([\d.]+)s', log)))}
        return (f'ready_s={ready_s:.1f} (spawn to healthy: weights_s='
                f'{stamp["weights_s"]} then KV allocation, warm-up '
                f'compile+run warm_s={stamp["warm_s"]}) '
                f'short={first["seconds"]}s long={long_["seconds"]}s '
                f'(first use of its prefill buckets) '
                f'repeat={again["seconds"]}s '
                f'compiled={m["compiled_programs"]} '
                f'device_memory_bytes={mem} '
                f'cache={m["compile_cache_dir"]}')
    finally:
        if lb is not None:
            r.stop(lb)
        r.stop(server)
        print(f'[smoke] {name}: server exit code {server.returncode}',
              flush=True)


def phase_serve(r: Runner) -> str:
    return _serve(r, 'serve', r.sizes['serve'], tp=1)


def phase_serve4(r: Runner) -> str:
    return _serve(r, 'serve4', r.sizes['serve_tp'], tp=r.sizes['tp'])


_BOOT_RE = re.compile(
    r'platform=(\S+) device_kind=(.+?) devices=(\d+) mesh .*'
    r'attention=(\S+) compile_cache=(\S+)')
_STEP_RE = re.compile(r'step (\d+)/\d+ loss=(\S+)')
_FIRST_RE = re.compile(r'first step \(compile \+ run\): ([\d.]+)s')
_PLACE_RE = re.compile(r'placement: (\{.*\})')


def _train(r: Runner, name: str, extra: List[str],
           n_devices: Optional[int]) -> str:
    """``n_devices``: the mesh the flags ask for, whose placement is
    then checked; None takes whatever mesh the defaults make."""
    r.run(name, [sys.executable, '-m', 'skypilot_tpu.train.run',
                 *r.sizes['train'], *extra], timeout=900)
    log = r.tail(name, 10_000)
    boot, first = _BOOT_RE.search(log), _FIRST_RE.search(log)
    place = _PLACE_RE.search(log)
    if not (boot and first and place):
        raise PhaseFailed(f'{name}: boot, first-step or placement line '
                          f'missing from the log')
    platform, kind, count, attention, cache = boot.groups()
    if platform != r.sizes['platform']:
        raise PhaseFailed(f'{name}: ran on {platform} x{count}')
    if attention != r.sizes['attention']:
        raise PhaseFailed(f'{name}: attention={attention}, expected '
                          f'{r.sizes["attention"]}')
    r.check_cache_dir(cache)
    losses = [float(v) for _, v in _STEP_RE.findall(log)]
    if (len(losses) != 6 or not all(math.isfinite(v) for v in losses)
            or not losses[-1] < losses[0]):
        raise PhaseFailed(f'{name}: losses {losses} are not six finite '
                          f'values that fall')
    placement = json.loads(place.group(1))
    shards = placement['w_gate_shards_per_device']
    mem = placement['bytes_in_use']
    if n_devices is not None:
        if len(shards) != n_devices:
            raise PhaseFailed(f'{name}: w_gate has shards on devices '
                              f'{sorted(shards)}, expected {n_devices}')
        used = mem[:n_devices]
        if all(b is not None for b in used) and not (
                min(used) > 0 and max(used) < 2 * min(used)):
            raise PhaseFailed(f'{name}: device memory is lopsided: '
                              f'{mem}')
    return (f'{kind} x{count} attention={attention} '
            f'first_step_s={first.group(1)} (compile + run) '
            f'loss {losses[0]:.4f} -> {losses[-1]:.4f} '
            f'shards={shards} bytes_in_use={mem} cache={cache}')


def phase_train(r: Runner) -> str:
    return _train(r, 'train', [], n_devices=None)


def phase_train4(r: Runner) -> str:
    return _train(r, 'train4', ['--fsdp', '2', '--tp', '2'], n_devices=4)


def phase_graft(r: Runner) -> str:
    t0 = time.time()
    r.run('graft', [sys.executable, os.path.join(ROOT,
                                                 '__graft_entry__.py'),
                    '4'], timeout=900)
    lines = [ln for ln in r.tail('graft', 10_000).splitlines()
             if ln.startswith('[dryrun]')]
    want = f'platform={r.sizes["platform"]}'
    if (not lines or not all(want in ln for ln in lines)
            or 'all checks passed' not in lines[-1]):
        raise PhaseFailed(f'graft: expected every [dryrun] line on '
                          f'{want} and a final pass, got {lines}')
    return f'{len(lines)} [dryrun] lines on {want} in ' \
           f'{time.time() - t0:.0f}s'


PHASES: Dict[str, Callable[[Runner], str]] = {
    'kernels': phase_kernels, 'serve': phase_serve, 'train': phase_train,
    'train4': phase_train4, 'serve4': phase_serve4, 'graft': phase_graft,
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--rehearse-cpu', action='store_true',
                        help='tiny CPU rehearsal of the same phases '
                             '(explicit; never a fallback)')
    parser.add_argument('--phases', default=None,
                        help='comma-separated subset of '
                             f'{",".join(PHASES)} (debugging: the final '
                             'line then lists what ran)')
    parser.add_argument('--out', default=None,
                        help='output directory (default '
                             'chiprun_out/chip_smoke under the checkout)')
    parser.add_argument('--child', nargs='+', default=None,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args.child:
        kind, rest = args.child[0], args.child[1:]
        if kind == 'probe':
            return _child_probe()
        if kind == 'lb':
            return _child_lb(rest[0], int(rest[1]), rest[2])
        if kind == 'kernels':
            return _child_kernels(args.rehearse_cpu, rest[0])
        raise SystemExit(f'unknown child {kind!r}')

    out_dir = os.path.abspath(args.out or os.path.join(
        ROOT, 'chiprun_out',
        'chip_smoke_rehearsal' if args.rehearse_cpu else 'chip_smoke'))
    os.makedirs(out_dir, exist_ok=True)
    runner = Runner(out_dir, args.rehearse_cpu)
    t_start = time.time()
    try:
        try:
            device = phase_probe(runner)
        except PHASE_ERRORS as e:
            print(f'[smoke] probe: FAIL {e}\n' + runner.tail('probe'),
                  flush=True)
            return 1
        print(f'[smoke] probe: PASS platform={device["platform"]} '
              f'device_kind={device["device_kind"]!r} '
              f'count={device["count"]} jax={device["jax"]} '
              f'env={device["env"]} '
              f'cache={device["compile_cache_dir"]} '
              f'device_order={device["device_order"]}', flush=True)
        if args.phases:
            names = [p for p in args.phases.split(',') if p]
            unknown = [p for p in names if p not in PHASES]
            if unknown:
                raise SystemExit(f'unknown phases {unknown}')
        else:
            names = list(ONE_CHIP_PHASES)
            if device['count'] >= 4:
                names += FOUR_CHIP_PHASES
        failed = []
        for name in names:
            t0 = time.time()
            try:
                note = PHASES[name](runner)
                print(f'[smoke] {name}: PASS in {time.time() - t0:.0f}s '
                      f'{note}', flush=True)
            except PHASE_ERRORS as e:
                failed.append(name)
                print(f'[smoke] {name}: FAIL in {time.time() - t0:.0f}s '
                      f'{type(e).__name__}: {e}\n--- tail of '
                      f'{runner.log_path(name)} ---\n'
                      + runner.tail(name), flush=True)
            finally:
                runner.stop_all()
        print(f'[smoke] total {time.time() - t_start:.0f}s; logs in '
              f'{out_dir}', flush=True)
        if failed:
            print(f'[smoke] FAILED phases: {failed}', flush=True)
            return 1
    finally:
        runner.stop_all()
    result: Dict[str, Any] = {
        'ok': True,
        'device': {'platform': device['platform'],
                   'kind': device['device_kind'],
                   'count': device['count']}}
    if args.phases:
        result['phases'] = names
    if args.rehearse_cpu:
        result['rehearsal'] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
