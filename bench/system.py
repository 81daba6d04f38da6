"""The system under test, built from a configuration file.

This is the only module of the benchmark that imports the program
(``repro``).  It turns a configuration file into the served pipeline: one
``DiffusionEngine`` whose weights are replaced by the benchmark's own
(``weights.make``), behind ``ClusterRouter(engine, replicas=1,
slots_per_replica=S)``.  The window consumes ``ClusterRouter.stream``.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

# host spans the benchmark records around each call into the engine, so
# that device idle gaps can be attributed to what the host was doing
ENGINE_CALLS = ("admit", "slot_step", "finished_slots", "decode_slots",
                "retire")


# configuration groups named apart from the ``PipelineConfig`` field they
# fill; every other top-level group named as a field fills that field
FIELD_OF_GROUP = {"denoiser": "unet", "sampler": "ddim"}


def pipeline_config(cfg: dict):
    """The program's ``PipelineConfig`` for a configuration file.

    The ``denoiser`` group is built into the config class of the family it
    names, as the program registers it (``repro.diffusion.denoiser``); any
    other top-level group named as a ``PipelineConfig`` field (``text``,
    ``vae``, ``sampler`` for ``ddim``) into the class that field holds by
    default.  Inside a group, lists become tuples and a nested object
    becomes the dataclass its field holds by default, recursively.
    """
    from repro.core.policies import ServePolicies
    from repro.diffusion import denoiser
    from repro.diffusion.pipeline import PipelineConfig

    den = dict(cfg["denoiser"])
    family = den.pop("family")
    tips_threshold = den.pop("tips_threshold")
    denoiser._ensure_registered()
    if family not in denoiser._REGISTRY:
        raise ValueError(f"denoiser family {family!r} is not registered; "
                         f"the program registers "
                         f"{sorted(denoiser._REGISTRY)}")
    fields = {f.name: f for f in dataclasses.fields(PipelineConfig)}
    built = {}
    for group, values in cfg.items():
        name = FIELD_OF_GROUP.get(group, group)
        if name not in fields:
            continue
        if name in built:
            raise ValueError(f"two configuration groups fill "
                             f"PipelineConfig.{name}")
        if group == "denoiser":
            built[name] = _build(denoiser._REGISTRY[family].config_cls, den)
        else:
            built[name] = _build(type(_default(fields[name])), values)
    serve = cfg["serve"]
    policies = ServePolicies.parse(
        kernels=serve["kernels"],
        tips=f"{serve['tips']},threshold={tips_threshold}",
        reuse=serve["reuse"])
    return policies.apply(PipelineConfig(**built))


def _default(field):
    if field.default_factory is not dataclasses.MISSING:
        return field.default_factory()
    return field.default


def _build(klass, group: dict):
    """``klass`` from a configuration group (see ``pipeline_config``)."""
    fields = {f.name: f for f in dataclasses.fields(klass)}
    unknown = set(group) - set(fields)
    if unknown:
        raise ValueError(f"{klass.__name__} has no field {sorted(unknown)}")

    def value(key, v):
        if isinstance(v, dict):
            default = _default(fields[key])
            if not dataclasses.is_dataclass(default):
                raise ValueError(f"{klass.__name__}.{key} holds no dataclass "
                                 f"by default, so it takes no group")
            return _build(type(default), v)
        return _tuples(v)
    return klass(**{k: value(k, v) for k, v in group.items()})


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def abstract_weights(cfg: dict) -> dict:
    """Shapes and dtypes of the served program's weights, nothing made."""
    import jax

    from repro.diffusion.denoiser import make_denoiser
    from repro.diffusion.text_encoder import init_text_encoder_params
    from repro.diffusion.vae import init_vae_params

    pipe = pipeline_config(cfg)
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda: {
        "text": init_text_encoder_params(key, pipe.text),
        "denoiser": make_denoiser(pipe.unet).init_params(key),
        "vae": init_vae_params(key, pipe.vae)})


class System:
    """Engine and router for one cell; ``weights`` are the benchmark's."""

    def __init__(self, cfg: dict, weights_fn, spans: bool = False):
        import jax

        from repro.diffusion.engine import DiffusionEngine
        from repro.launch.router import ClusterRouter

        self.cfg = cfg
        self.slots = cfg["serve"]["slots"]
        built = []

        def build():
            # the engine draws weights of its own as it is made; made under
            # eval_shape, they are shapes only, replaced by the benchmark's
            eng = DiffusionEngine(pipeline_config(cfg),
                                  key=jax.random.PRNGKey(0))
            built.append(eng)
            return {"text": eng.text_params, "denoiser": eng.unet_params,
                    "vae": eng.vae_params}
        abstract = jax.eval_shape(build)
        self.engine = eng = built[0]
        self.weights = weights_fn(abstract)
        eng.text_params = self.weights["text"]
        eng.unet_params = self.weights["denoiser"]
        eng.vae_params = self.weights["vae"]
        self.router = ClusterRouter(eng, replicas=1,
                                    slots_per_replica=self.slots)
        if spans:
            for name in ENGINE_CALLS:
                setattr(eng, name, _annotated(name, getattr(eng, name)))

    def serve(self, t0: float, deadline: float, starts, steps: int) -> None:
        """Wrap the engine's slot step for the window, before the stream's
        first step; ``t0`` is the serving clock's 0 (``perf_counter``).

        * From the first step after ``deadline`` (moved by ``pause``) the
          step raises ``StreamDeadline``: the window's end in a standing
          queue, and a bound on the drain of a stream that stopped
          completing requests.
        * ``self.step_ends`` gets the end of every step on the serving
          clock (the step blocks until its result is ready).
        * For each slot, the first request admitted into it at or after
          ``starts[slot]`` (serving clock) is recorded: after each of its
          steps, the latents of its row.  Inside the window a step's
          latents stay on the device (one copy a step, since the next
          step donates them); ``recorded()`` reads them back afterwards.
          The harness reports admissions through ``admitted(rid, slot,
          t_s)``.
        """
        import jax.numpy as jnp

        step = self.engine.slot_step
        rows, self._slot_rid = {}, {}
        self._starts = list(starts)
        self._deadline = deadline
        self.step_ends = []

        def slot_step(state):
            if time.perf_counter() >= self._deadline:
                raise StreamDeadline
            state = step(state)
            self.step_ends.append(time.perf_counter() - t0)
            if self._slot_rid:
                snap = jnp.copy(state.latents)
                for slot, rid in list(self._slot_rid.items()):
                    rows[rid].append((snap, slot))
                    if len(rows[rid]) == steps:
                        del self._slot_rid[slot]
            return state
        self.engine.slot_step = slot_step
        self._recorded = rows

    def pause(self, at: float, seconds: float) -> None:
        """Leave ``seconds`` from ``at`` (serving clock) out of the window,
        for work that held the stream still (the profiler's start and
        stop): the deadline, and every sample start time not yet reached,
        move on by them."""
        self._deadline += seconds
        self._starts = [s + seconds if s is not None and s >= at else s
                        for s in self._starts]

    def recorded(self) -> dict:
        """``{rid: [x_1, ..., x_k]}`` as host arrays, x_i being the
        request's row after its i-th step (k < steps where the stream
        stopped first); called once the window has closed."""
        import jax
        import numpy as np

        host = {}
        out = {}
        for rid, snaps in self._recorded.items():
            out[rid] = []
            for snap, slot in snaps:
                if id(snap) not in host:
                    host[id(snap)] = (snap, np.asarray(jax.device_get(snap)))
                out[rid].append(host[id(snap)][1][slot])
        self._recorded = {}
        return out

    def admitted(self, rid: int, slot: int, t_s: float) -> int:
        """Note an admission at ``t_s`` on the serving clock; returns the
        index of the request's first step in ``step_ends``."""
        if self._starts[slot] is not None and t_s >= self._starts[slot]:
            self._starts[slot] = None
            self._recorded[rid] = []
            self._slot_rid[slot] = rid
        return len(self.step_ends)

    def kernel_policy(self) -> dict:
        return self.engine.cfg.unet.effective_kernel_policy().describe()

    def warmup(self) -> float:
        """Every program the window can run, compiled and run once.

        The router's own warm-up (slot step at S, encode, admit, one decode
        per power of two), plus what retirement runs eagerly for any number
        k of slots finishing in one round: the concatenation of the
        power-of-two decode chunks, the per-row slices of it, and the
        ``retire`` scatter.  Returns seconds.
        """
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        self.router.warmup()
        eng, s = self.engine, self.slots
        state = eng.init_slots(s)
        px = 8 * self.cfg["denoiser"]["latent_size"]
        row = (px, px, self.cfg["vae"]["out_channels"])
        for k in range(1, s + 1):
            parts, i = [], 0
            while i < k:
                c = 1 << ((k - i).bit_length() - 1)
                parts.append(jnp.zeros((c,) + row, jnp.float32))
                i += c
            imgs = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)
            jax.block_until_ready([imgs[j:j + 1] for j in range(k)])
            jax.block_until_ready(eng.retire(state, list(range(k))).active)
        jax.block_until_ready(jnp.copy(state.latents))   # the recorder's copy
        return time.perf_counter() - t0

    def step_seconds(self, steps: int = 3) -> float:
        """Median wall time of one slot step at S (warm)."""
        import numpy as np

        eng = self.engine
        state = eng.init_slots(self.slots)
        walls = []
        for _ in range(steps):
            state = eng.slot_step(state)
            walls.append(eng.last_wall_s)
        return float(np.median(walls))

    def requests(self, toks, uncond, lat, due) -> list:
        from repro.launch.scheduler import Request

        use_cfg = self.cfg["sampler"]["guidance_scale"] != 1.0
        return [Request(rid=i, tokens=toks[i:i + 1], arrival_s=float(t),
                        latents=lat[i], uncond_tokens=uncond if use_cfg
                        else None)
                for i, t in enumerate(due)]

    def close(self):
        self.router = self.engine = self.weights = None


class StreamDeadline(Exception):
    """The stream's deadline passed (see ``System.serve``)."""


def _annotated(name, fn):
    import jax

    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            return fn(*a, **kw)
    return call
