"""Compile-once serving: the AOT program store (DESIGN.md §13).

The paper's thesis — do the expensive work once at install time so the
runtime stage is lookup-only — applied to XLA programs themselves.  The
engine's (batch-bucket x length-bucket) x {prefill, decode, prefill_row}
grid used to be a pile of ad-hoc ``jax.jit`` wrappers compiled lazily on
first traffic; a :class:`ProgramStore` instead AOT-lowers each program
from ShapeDtypeStructs via ``jit(...).lower(...).compile()`` and keeps
the compiled executable:

* **in memory** — re-acquiring a key is a dict hit (``source='memory'``),
  exactly the old warm-program behavior;
* **on disk** — executables round-trip through
  ``jax.experimental.serialize_executable``, keyed by (config
  fingerprint, code fingerprint, program kind, bucket grid cell, mesh
  signature, argument-structure digest).  A cold engine whose grid was
  populated by ``install --precompile`` performs ZERO traces on first
  traffic: every program deserializes in milliseconds
  (``source='disk'``).

Invalidation is by construction: the key digests the model config, the
``repro`` package source bytes, the pytree structure of every argument
(including each ``PackedTensor``'s block shapes and stamped kernel/
schedule specs) and the mesh axes — change a plan, a pack layout, a
config field or the model code and the old entry simply stops matching.

Sharded serving (``Engine(mesh=...)``) lowers through the same seam with
explicit ``in_shardings``/``out_shardings`` (params from
``sharding/rules.py``, cache/batch/token placement from
:class:`~repro.sharding.context.ShardCtx`), so tensor-parallel programs
are stored, restored and collective-audited exactly like single-device
ones.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import os
import pickle
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.variants import grammar as _grammar
from repro.resilience import degrade, failpoints
from repro.sharding.context import ShardCtx, sharding_ctx
from repro.sharding.rules import ShardingOptions

log = logging.getLogger(__name__)

# bump when the on-disk payload layout changes
PROGRAM_SCHEMA = 1

# donated argument positions per program kind: cache buffers are reused
# in place (prefill fills the fresh cache it is handed, so the input and
# output caches never both occupy the device)
DONATE = {"prefill": (2,), "decode": (1,), "prefill_row": (2,)}

# batch-dict leaf -> logical activation axes (ShardCtx placement)
BATCH_AXES = {"tokens": ("batch", "seq"), "pad": ("batch",),
              "embeds": ("batch", "seq", "embed"),
              "enc_frames": ("batch", "seq", "embed")}


# Fixed in-checkout cache root (git-ignored) for when the environment
# names no compilation-cache directory.  A fixed path, because the path
# is part of what a cache is found by: a directory that moves never hits.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".cache"


def enable_compilation_cache() -> Path:
    """Turn on JAX's persistent compilation cache and return its
    directory; entry points call this when they start (never at import).
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no
    other directory is set here; else ``.cache/jax`` in the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    path = Path(env) if env else CHECKOUT_CACHE / "jax"
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    # cache every program: the install sweep compiles many small kernels
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def program_cache_dir() -> Optional[Path]:
    """Resolve the persistent program-cache directory.

    ``REPRO_PROGRAM_CACHE``: a path, or ``off``/``0``/``none`` to disable
    persistence entirely.  Unset -> ``repro-programs`` under
    ``JAX_COMPILATION_CACHE_DIR`` when that is set, else
    ``.cache/programs`` in the checkout."""
    raw = os.environ.get("REPRO_PROGRAM_CACHE", "")
    if raw:
        if raw.lower() in ("off", "0", "none"):
            return None
        return Path(raw)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    return Path(env) / "repro-programs" if env else CHECKOUT_CACHE / "programs"


_CODE_FP: Optional[str] = None


def code_fingerprint() -> str:
    """Digest of every ``repro`` source file (path + bytes).  Stored
    programs replay baked-in traced semantics, so ANY code change must
    invalidate them — shape-only keys would happily replay a stale
    program after a model-code fix."""
    global _CODE_FP
    if _CODE_FP is None:
        import repro
        root = Path(repro.__file__).resolve().parent
        h = hashlib.sha256()
        for f in sorted(root.rglob("*.py")):
            h.update(str(f.relative_to(root)).encode())
            h.update(f.read_bytes())
        _CODE_FP = h.hexdigest()
    return _CODE_FP


def config_fingerprint(cfg) -> str:
    """Model-config digest: every field participates (the config is a
    frozen dataclass whose repr is deterministic), plus the jax version
    and backend the executable was compiled for."""
    blob = f"{cfg!r}|jax={jax.__version__}|backend={jax.default_backend()}"
    return hashlib.sha256(blob.encode()).hexdigest()


def mesh_signature(mesh, opts: Optional[ShardingOptions]) -> str:
    """Key component for the mesh: axis names/sizes + device kinds +
    every ShardingOptions knob.  Works for AbstractMesh too (packing
    divisors shape the programs even without devices)."""
    if mesh is None:
        return "unsharded"
    axes = ",".join(f"{k}={v}" for k, v in dict(mesh.shape).items())
    devs = getattr(mesh, "devices", None)
    kinds = sorted({d.device_kind for d in devs.flat}) if devs is not None \
        else ["abstract"]
    return f"{axes}|{kinds}|{opts!r}"


def tree_digest(tree) -> str:
    """Structure digest of an argument pytree: treedef repr (which
    includes PackedTensor aux data — block layout and stamped
    kernel/schedule specs) + every leaf's shape/dtype.  Values never
    participate, so ShapeDtypeStructs and real arrays digest alike."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    h = hashlib.sha256(repr(treedef).encode())
    for leaf in flat:
        h.update(f"|{tuple(jnp.shape(leaf))}:{leaf.dtype}".encode())
    return h.hexdigest()


def program_name(kind: str, bucket: int, tokens: int) -> str:
    """The stable name a serving program is lowered under (its XLA module
    is ``jit_<name>``), so a profile tells the programs apart by module
    name alone: ``decode_step_b<slots>``, ``prefill_row_b<slots>_t<lb>``,
    ``prefill_b<batch>_t<length>``."""
    if kind == "decode":
        return f"decode_step_b{bucket}"
    return f"{kind}_b{bucket}_t{tokens}"


def _named(fn, name: str):
    """``fn`` under the function name ``name`` (what ``jax.jit`` names
    the module after)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


def aot_lower(fn, args, *, in_shardings=None, out_shardings=None,
              donate_argnums=()):
    """The ONE lowering helper: ``jit(fn).lower(*args)`` with optional
    shardings/donation.  ``args`` may be ShapeDtypeStructs (install-time
    precompile, dryrun) or real arrays (first-traffic fallback) — avals
    are identical either way, so the compiled program is too.  Both the
    ProgramStore and ``launch/dryrun.py`` report costs from artifacts
    produced here."""
    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    if donate_argnums:
        kw["donate_argnums"] = donate_argnums
    return jax.jit(fn, **kw).lower(*args)


@dataclasses.dataclass
class Program:
    """One compiled serving program handle.

    ``cold`` is True the FIRST time this store instance hands out the
    key — whether the executable was traced or deserialized — so the
    engine/scheduler charge compile time per store exactly like the old
    ``_warm_programs`` set did (virtual-clock telemetry stays
    deterministic regardless of disk state).  ``source`` says what
    actually happened: ``traced`` (lower+compile), ``disk``
    (deserialized), ``memory`` (reused handle)."""
    kind: str
    key: str
    fn: object                  # the callable executable
    executable: object          # jax.stages.Compiled (HLO access)
    cold: bool
    source: str
    compile_s: float            # store-side acquire cost (lower+compile
    #                             or deserialize), telemetry only


class ProgramStore:
    """AOT-compiled serving programs for one model (+ optional mesh).

    ``param_shardings`` is required in mesh mode (the engine computes it
    once from the packed tree); ``cache_dir=False`` disables persistence,
    ``None`` resolves ``REPRO_PROGRAM_CACHE``/the default directory."""

    def __init__(self, model, *, mesh=None, opts: Optional[ShardingOptions] = None,
                 param_shardings=None, cache_dir=None):
        self.model = model
        self.mesh = mesh if isinstance(mesh, Mesh) else None
        self.lowering_mesh = mesh      # Abstract meshes still gate packing
        self.opts = opts or ShardingOptions()
        self.param_shardings = param_shardings
        if cache_dir is False:
            self.cache_dir = None
        else:
            self.cache_dir = Path(cache_dir) if cache_dir else program_cache_dir()
        self._fns = {"prefill": model.prefill, "decode": model.decode_step,
                     "prefill_row": model.prefill_row}
        # the kernel-synthesis grammar version rides in the fingerprint:
        # a grammar change can alter what any tuned plan lowers to, so
        # every disk-cached executable must miss cleanly and recompile
        # (DESIGN.md §14)
        self._fingerprint = (config_fingerprint(model.cfg)
                             + code_fingerprint()
                             + _grammar.GRAMMAR_VERSION)
        self._programs: dict[str, Program] = {}
        # compile_s covers trace + lower + XLA compile; lower_s is its
        # trace + lower part, which JAX's persistent cache cannot skip
        self._stats = {"traced": 0, "from_disk": 0, "reused": 0,
                       "compile_s": 0.0, "lower_s": 0.0, "load_s": 0.0}

    # -- keys ------------------------------------------------------------

    def key_for(self, kind: str, args, *, bucket: int, tokens: int) -> str:
        h = hashlib.sha256()
        h.update(self._fingerprint.encode())
        h.update(f"|{PROGRAM_SCHEMA}|{kind}|{DONATE[kind]}".encode())
        h.update(mesh_signature(self.lowering_mesh, self.opts).encode())
        for a in args:
            h.update(tree_digest(a).encode())
        return f"{kind}_b{bucket}_t{tokens}_{h.hexdigest()[:16]}"

    # -- sharding plumbing ----------------------------------------------

    def _ctx(self) -> ShardCtx:
        return ShardCtx(self.mesh, self.opts)

    def batch_shardings(self, batch):
        ctx = self._ctx()
        return {k: NamedSharding(self.mesh, ctx.spec_for(
            BATCH_AXES.get(k, (None,) * jnp.ndim(v)), jnp.shape(v)))
            for k, v in batch.items()}

    def cache_shardings(self, cache):
        from repro.sharding.rules import cache_pspecs
        specs = cache_pspecs(self.model.cfg, cache, self.mesh, self.opts)
        return {k: NamedSharding(self.mesh, s) for k, s in specs.items()}

    def tokens_sharding(self, tokens):
        ctx = self._ctx()
        return NamedSharding(self.mesh, ctx.spec_for(
            ("batch",) + (None,) * (jnp.ndim(tokens) - 1), jnp.shape(tokens)))

    def shardings_for(self, kind: str, args):
        """(in_shardings, out_shardings) for one program, or (None, None)
        off-mesh.  Outputs pin logits replicated (the host argmaxes them
        every step) and the cache to its OWN input shardings, so a decode
        output feeds the next decode input without resharding."""
        if self.mesh is None:
            return None, None
        if self.param_shardings is None:
            raise ValueError("mesh-mode ProgramStore needs param_shardings")
        logits = NamedSharding(self.mesh, P())
        scalar = NamedSharding(self.mesh, P())
        if kind == "prefill":
            c_sh = self.cache_shardings(args[2])
            return ((self.param_shardings, self.batch_shardings(args[1]),
                     c_sh), (logits, c_sh))
        if kind == "decode":
            c_sh = self.cache_shardings(args[1])
            return ((self.param_shardings, c_sh,
                     self.tokens_sharding(args[2])), (logits, c_sh))
        c_sh = self.cache_shardings(args[2])
        return ((self.param_shardings, self.batch_shardings(args[1]),
                 c_sh, scalar, scalar), (logits, c_sh))

    def place(self, tree, shardings):
        """device_put helper (no-op off-mesh)."""
        if self.mesh is None or shardings is None:
            return tree
        return jax.device_put(tree, shardings)

    # -- acquire ---------------------------------------------------------

    def program(self, kind: str, args, *, bucket: int, tokens: int) -> Program:
        """Load-or-compile the program for ``fn(*args)``.

        ``args`` may be real arrays (serving) or ShapeDtypeStructs
        (install --precompile): only structure participates in the key
        and the lowering.  Memory hit -> reused warm handle; disk hit ->
        deserialize; miss -> AOT lower+compile under serving/sharding
        contexts (TSMM routing and mesh constraints bake into the
        program), then persist."""
        key = self.key_for(kind, args, bucket=bucket, tokens=tokens)
        prog = self._programs.get(key)
        if prog is not None:
            self._stats["reused"] += 1
            return dataclasses.replace(prog, cold=False, source="memory",
                                       compile_s=0.0)
        t0 = time.perf_counter()
        compiled = self._load(key)
        source = "disk"
        if compiled is None:
            source = "traced"
            structs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), args)
            in_sh, out_sh = self.shardings_for(kind, args)
            from repro.core.linear import serving_ctx
            with serving_ctx(), sharding_ctx(self.lowering_mesh, self.opts):
                fn = _named(self._fns[kind],
                            program_name(kind, bucket, tokens))
                lowered = aot_lower(
                    fn, structs, in_shardings=in_sh,
                    out_shardings=out_sh, donate_argnums=DONATE[kind])
                self._stats["lower_s"] += time.perf_counter() - t0
                compiled = lowered.compile()
            self._save(key, kind, compiled)
        dt = time.perf_counter() - t0
        self._stats["traced" if source == "traced" else "from_disk"] += 1
        self._stats["compile_s" if source == "traced" else "load_s"] += dt
        prog = Program(kind=kind, key=key, fn=compiled, executable=compiled,
                       cold=True, source=source, compile_s=dt)
        self._programs[key] = prog
        return prog

    # -- persistence -----------------------------------------------------

    def _path(self, key: str) -> Optional[Path]:
        return self.cache_dir / f"{key}.prog" if self.cache_dir else None

    def _load(self, key: str):
        path = self._path(key)
        if path is None or not path.exists():
            return None
        try:
            from jax.experimental import serialize_executable as se
            failpoints.fp("programs.deserialize")
            rec = pickle.loads(failpoints.corrupt("programs.deserialize",
                                                  path.read_bytes()))
            if rec.get("schema") != PROGRAM_SCHEMA:
                return None
            # bind to the devices the program was compiled for: left
            # unset, the loader would spread it over every local device
            devs = (list(self.mesh.devices.flat) if self.mesh is not None
                    else jax.devices()[:1])
            return se.deserialize_and_load(*rec["payload"],
                                           execution_devices=devs)
        except Exception as e:  # noqa: BLE001 — any failure = recompile
            log.warning("program cache: dropping unreadable %s (%s)",
                        path.name, e)
            # rung of the §16 ladder: AOT disk program -> retrace
            degrade.record("program.disk", key=key, fallback="retrace",
                           error=str(e))
            return None

    def _save(self, key: str, kind: str, compiled) -> None:
        path = self._path(key)
        if path is None:
            return
        try:
            from jax.experimental import serialize_executable as se
            payload = se.serialize(compiled)
            rec = {"schema": PROGRAM_SCHEMA, "kind": kind, "key": key,
                   "jax": jax.__version__,
                   "backend": jax.default_backend(), "payload": payload}
            path.parent.mkdir(parents=True, exist_ok=True)
            failpoints.fp("programs.serialize.before_replace")
            fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                pickle.dump(rec, f)
            os.replace(tmp, path)      # atomic: concurrent warmers race safely
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            log.warning("program cache: could not persist %s (%s)", key, e)
            degrade.record("program.persist", key=key,
                           fallback="memory-only", error=str(e))

    # -- telemetry -------------------------------------------------------

    def stats(self) -> dict:
        out = dict(self._stats)
        out["programs"] = len(self._programs)
        out["cache_dir"] = str(self.cache_dir) if self.cache_dir else None
        return out

    def handles(self) -> list:
        """Every program this store has handed out (first acquisition
        each), for HLO inspection."""
        return list(self._programs.values())

    def report(self) -> list:
        """Per-program rows (key, kind, source, acquire seconds) — the
        cold-start benchmark's per-bucket breakdown."""
        return [{"key": p.key, "kind": p.kind, "source": p.source,
                 "compile_s": p.compile_s}
                for p in self._programs.values()]

    def collectives(self, prog: Program) -> dict:
        """Trip-count-aware per-device collective accounting of one
        stored program (the CI contract for sharded decode)."""
        from repro.analysis.hlo_collectives import collective_bytes
        return collective_bytes(prog.executable.as_text())


# ---------------------------------------------------------------------------
# install-time precompilation
# ---------------------------------------------------------------------------


def abstract_serving_args(model, axes, buckets, mesh=None, opts=None):
    """(packed-params struct, logical axes) via shape-only evaluation —
    the exact tree a real Engine packs at load, so program keys match by
    construction."""
    from repro.serve.engine import pack_tree_for_serving

    def init_shapes(rng):
        p, _ = model.init(rng)
        return p

    params = jax.eval_shape(init_shapes, jax.random.PRNGKey(0))
    packed = jax.eval_shape(
        lambda p: pack_tree_for_serving(p, axes, tuple(buckets), mesh,
                                        opts)[0], params)
    return packed


def _batch_struct(cfg, b: int, lb: int, *, pad: bool) -> dict:
    out = {"tokens": jax.ShapeDtypeStruct((b, lb), jnp.int32)}
    if pad:
        out["pad"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    if cfg.embeds_input:
        out["embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if getattr(cfg, "is_encoder_decoder", False):
        out["enc_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return out


def precompile_grid(model, axes, *, buckets, lengths, max_len: int,
                    mesh=None, opts: Optional[ShardingOptions] = None,
                    store: Optional[ProgramStore] = None,
                    cache_dir=None) -> list:
    """Populate the program cache with the full serving grid — the
    ``install --precompile`` phase (DESIGN.md §13).

    Enumerates exactly the programs a same-shaped Engine acquires at
    serve time: per batch bucket one decode step; per (bucket x length)
    cell a prefill with and (ragged families) without per-row pad
    masking; per (slot-bucket x length) cell one ``prefill_row`` ragged
    admission.  Returns per-program report rows."""
    cfg = model.cfg
    opts = opts or ShardingOptions()
    if store is None:
        p_sh = None
        packed = abstract_serving_args(model, axes, buckets, mesh, opts)
        if isinstance(mesh, Mesh):
            from repro.sharding.rules import param_shardings
            p_sh = param_shardings(axes, packed, mesh, opts)
        store = ProgramStore(model, mesh=mesh, opts=opts,
                             param_shardings=p_sh, cache_dir=cache_dir)
    else:
        packed = abstract_serving_args(model, axes, buckets, store.mesh
                                       or mesh, store.opts)
    ragged = (model.prefill_row is not None and not cfg.embeds_input
              and not getattr(cfg, "is_encoder_decoder", False))
    rows = []

    def acquire(kind, args, bucket, tokens):
        prog = store.program(kind, args, bucket=bucket, tokens=tokens)
        rows.append({"kind": kind, "bucket": bucket, "tokens": tokens,
                     "key": prog.key, "source": prog.source,
                     "compile_s": prog.compile_s})
        return prog

    for bb in buckets:
        cache = jax.eval_shape(lambda b=bb: model.init_cache(b, max_len))
        tok = jax.ShapeDtypeStruct((bb, 1), jnp.int32)
        acquire("decode", (packed, cache, tok), bb, 1)
        for lb in lengths:
            # uniform exact-length groups serve without a pad mask;
            # ragged ones carry batch["pad"] — two distinct programs
            acquire("prefill", (packed, _batch_struct(cfg, bb, lb, pad=False),
                                cache), bb, lb)
            if ragged:
                acquire("prefill",
                        (packed, _batch_struct(cfg, bb, lb, pad=True), cache),
                        bb, lb)
                row = jax.ShapeDtypeStruct((), jnp.int32)
                acquire("prefill_row",
                        (packed, _batch_struct(cfg, 1, lb, pad=True), cache,
                         row, row), bb, lb)
    return rows
