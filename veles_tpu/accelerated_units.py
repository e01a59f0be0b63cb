"""AcceleratedUnit — the jit compilation layer.

Rebuild of veles/accelerated_units.py (130-866).  The reference bound
per-backend methods (``ocl_run``/``cuda_run``/``numpy_run``), assembled
kernel source with Jinja2 and cached compiled binaries per device.  The
TPU-native design replaces all of that with *tracing*:

- An accelerated unit declares the attributes it READS and WRITES and
  implements one **pure** :meth:`AcceleratedUnit.step` over jax values.
  There is no per-backend code: the same traced function runs on TPU and
  on (virtual multi-device) CPU, which is what made the reference keep
  three kernel dialects in sync.
- ``Array`` objects are the SSA registers between units: ``link_attrs``
  aliases an attribute to the upstream unit's Array, so the segment
  compiler can key the dataflow by Array identity.
- Consecutive accelerated units **fuse into one jitted XLA program**
  (:class:`FusedSegment`) — the north-star design decision (SURVEY.md §7):
  one device dispatch per workflow segment per minibatch instead of the
  reference's per-unit kernel launches.  Read-write (state) Arrays are
  donated so parameters update in place in HBM.
- The binary cache (ref: accelerated_units.py:605-673 tar.gz of PTX) is
  XLA's persistent compilation cache, enabled once per process.

Standalone (unfused) accelerated units still jit their own step; eager
mode (``root.common.engine.eager = True``) skips jit entirely for
debugging, like the reference's numpy fallback path.
"""

import os

import jax

from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.units import Unit
from veles_tpu.workflow import Workflow

#: where the compile cache lives when nothing outside says otherwise:
#: a FIXED path beside the package (the directory is part of the cache
#: key, so one that moves with a pid, a time or a temp name never hits)
CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

#: the directory this process last placed the cache at (None = left to
#: JAX_COMPILATION_CACHE_DIR); unset until the first call
_placed_compile_cache = []


def enable_persistent_compile_cache(path=None):
    """Place XLA's on-disk compile cache — replaces the reference's
    tar.gz kernel binary cache (ref: veles/accelerated_units.py:605-673)
    and is the ONE place this program decides where it goes:

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax already took the directory
      from the environment and nothing here overrides it (whoever runs
      the program places the cache from outside);
    - else ``path`` (the CLI's ``--compilation-cache`` /
      ``root.common.trace.compilation_cache_dir``);
    - else :data:`CHECKOUT_COMPILE_CACHE`.

    Either way every compile is persisted (the thresholds drop to
    zero): a relaunch re-pays even sub-second compiles otherwise, and
    compile_tracker labels the reloads ``cache="hit"``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        chosen = None
    else:
        chosen = str(
            path or root.common.trace.get("compilation_cache_dir")
            or CHECKOUT_COMPILE_CACHE)
    if _placed_compile_cache == [chosen]:
        return
    _placed_compile_cache[:] = [chosen]
    if chosen is not None:
        jax.config.update("jax_compilation_cache_dir", chosen)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache initializes lazily at the FIRST compile and then pins
    # its directory — re-point it if something already jitted
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


class AcceleratedUnit(Unit):
    """A unit whose run() is a pure traced function over its declared
    attributes (ref: veles/accelerated_units.py:130).

    Subclasses declare::

        READS  = ("input", "weights", "bias")   # consumed attrs (Arrays)
        WRITES = ("output", "weights", "bias")  # produced attrs

    and implement :meth:`step`.  An attr in both READS and WRITES is
    *state* — its buffer is donated to the compiled program so updates
    happen in place in HBM.
    """

    hide_from_registry = True

    READS = ()
    WRITES = ()
    #: units that override run() or mutate host state per-iteration set
    #: this False so fuse() leaves them standalone
    FUSABLE = True

    def __init__(self, workflow, **kwargs):
        super(AcceleratedUnit, self).__init__(workflow, **kwargs)
        self.device = None

    def init_unpickled(self):
        super(AcceleratedUnit, self).init_unpickled()
        self._jit_step_ = None
        self._segment_ = None

    @property
    def reads(self):
        return self.READS

    @property
    def writes(self):
        return self.WRITES

    # -- subclass contract ---------------------------------------------------

    def step(self, **tensors):
        """Pure function: ``{read attr: jax value} -> {write attr: jax
        value}``.  Traced under jit; no side effects, no Python branches
        on tensor values."""
        raise NotImplementedError(
            "%s must implement step()" % type(self).__name__)

    # -- lifecycle -----------------------------------------------------------

    def initialize(self, device=None, **kwargs):
        super(AcceleratedUnit, self).initialize(**kwargs)
        if device is not None:
            self.device = device
        enable_persistent_compile_cache()
        for attr in set(self.reads) | set(self.writes):
            arr = getattr(self, attr, None)
            if isinstance(arr, Array):
                arr.initialize(self.device)

    def run(self):
        if self._segment_ is not None:
            self._segment_.run_for(self)
        else:
            self._run_standalone()

    # -- standalone execution ------------------------------------------------

    def _gather(self):
        tensors = {}
        for attr in self.reads:
            val = getattr(self, attr)
            tensors[attr] = val.devmem if isinstance(val, Array) else val
        return tensors

    def _scatter(self, outputs):
        for attr, val in outputs.items():
            target = getattr(self, attr, None)
            if isinstance(target, Array):
                target.devmem = val
            else:
                setattr(self, attr, val)

    def _run_standalone(self):
        if root.common.engine.get("eager"):
            self._scatter(self.step(**self._gather()))
            return
        if self._jit_step_ is None:
            def stepper(donated, held):
                return self.step(**donated, **held)

            from veles_tpu.telemetry import track_jit
            self._jit_step_ = track_jit(
                "accel.%s" % type(self).__name__,
                jax.jit(stepper, donate_argnums=(0,)))
        tensors = self._gather()
        wset = set(self.writes)
        # state buffers are DONATED — hand over donation-safe ones
        # (host-aliased CPU buffers get detached, memory.py)
        donated = {}
        for a, t in tensors.items():
            if a not in wset:
                continue
            arr = getattr(self, a)
            donated[a] = arr.donatable_devmem() \
                if isinstance(arr, Array) else t
        held = {a: t for a, t in tensors.items() if a not in wset}
        self._scatter(self._jit_step_(donated, held))


class FusedSegment:
    """A maximal chain of accelerated units compiled into ONE jitted XLA
    program (the TPU answer to per-unit kernel dispatch, SURVEY.md §7).

    The scheduler still walks every unit's gates; the first member to run
    in an iteration executes the whole fused program, and the remaining
    members' run() calls are satisfied from it.
    """

    def __init__(self, units):
        self.units = list(units)
        self._pending = set()
        self._fallback = False
        self._jit = None
        # stable Array registry: id -> (index, array)
        self._arrays = []
        self._plan = None

    # -- planning ------------------------------------------------------------

    def _array_key(self, arr, registry):
        key = registry.get(id(arr))
        if key is None:
            key = len(self._arrays)
            registry[id(arr)] = key
            self._arrays.append(arr)
        return key

    def plan(self):
        """Resolve each unit's attrs to Array slots; classify slots into
        donated (read+written) / held (read-only) inputs and outputs."""
        registry = {}
        unit_io = []
        written = set()
        read_before_write = set()
        all_written = set()
        for u in self.units:
            ins, outs = {}, {}
            for attr in u.reads:
                arr = getattr(u, attr)
                if not isinstance(arr, Array):
                    raise TypeError("%s.%s is not an Array" % (u, attr))
                k = self._array_key(arr, registry)
                ins[attr] = k
                if k not in written:
                    read_before_write.add(k)
            for attr in u.writes:
                arr = getattr(u, attr)
                if not isinstance(arr, Array):
                    raise TypeError("%s.%s is not an Array" % (u, attr))
                k = self._array_key(arr, registry)
                outs[attr] = k
                written.add(k)
                all_written.add(k)
            unit_io.append((u, ins, outs))
        donated = sorted(read_before_write & all_written)
        held = sorted(read_before_write - all_written)
        outputs = sorted(all_written)
        self._plan = (unit_io, donated, held, outputs)
        return self._plan

    def _fused(self, donated_vals, held_vals):
        unit_io, donated, held, outputs = self._plan
        env = dict(zip(donated, donated_vals))
        env.update(zip(held, held_vals))
        for u, ins, outs in unit_io:
            tensors = {a: env[k] for a, k in ins.items()}
            result = u.step(**tensors)
            for a, k in outs.items():
                env[k] = result[a]
        return tuple(env[k] for k in outputs)

    # -- execution -----------------------------------------------------------

    def _execute(self):
        if self._plan is None:
            self.plan()
        _, donated, held, outputs = self._plan
        held_vals = tuple(self._arrays[k].devmem for k in held)
        if root.common.engine.get("eager"):
            donated_vals = tuple(self._arrays[k].devmem
                                 for k in donated)
            results = self._fused(donated_vals, held_vals)
        else:
            # the fused program donates the state slots — detach any
            # host-aliased buffer first (memory.py, ROUND6_NOTES.md)
            donated_vals = tuple(self._arrays[k].donatable_devmem()
                                 for k in donated)
            if self._jit is None:
                from veles_tpu.telemetry import track_jit
                self._jit = track_jit(
                    "fused:%s" % self.units[0].name,
                    jax.jit(self._fused, donate_argnums=(0,)))
            results = self._jit(donated_vals, held_vals)
        for k, v in zip(outputs, results):
            self._arrays[k].devmem = v

    def run_for(self, unit):
        """Called from each member's run().  The scheduler already
        enforces gates, so a member whose gate_skip/gate_block is set
        never arrives here — an iteration where any member's gate is
        engaged must therefore run per-unit, not fused."""
        if unit not in self._pending:
            # new iteration: either the previous one drained, or it never
            # did because a gate_block cut propagation mid-chain
            expected = {u for u in self.units
                        if not u.gate_skip and not u.gate_block}
            self._fallback = expected != set(self.units)
            if not self._fallback:
                self._execute()
            self._pending = expected
        self._pending.discard(unit)
        if self._fallback:
            unit._run_standalone()

    def __repr__(self):
        return "<FusedSegment %s>" % [u.name for u in self.units]


class AcceleratedWorkflow(Workflow):
    """Workflow owning a device; fuses accelerated-unit chains at
    initialize time (ref: veles/accelerated_units.py:827)."""

    hide_from_registry = True

    def __init__(self, workflow=None, **kwargs):
        super(AcceleratedWorkflow, self).__init__(workflow, **kwargs)
        self.device = None

    def init_unpickled(self):
        super(AcceleratedWorkflow, self).init_unpickled()
        self._segments_ = []

    def initialize(self, device=None, **kwargs):
        if device is None:
            from veles_tpu.backends import Device
            device = Device()
        self.device = device
        super(AcceleratedWorkflow, self).initialize(device=device, **kwargs)
        # always clear stale segment bindings from a previous initialize
        # (graph may have been rewired, or fusion turned off)
        self._segments_ = []
        for u in self.units:
            if isinstance(u, AcceleratedUnit):
                u._segment_ = None
        if root.common.engine.get("fuse", True):
            self.fuse()

    def fuse(self):
        """Find maximal SINGLE-ENTRY convex regions of accelerated units
        and compile each into a :class:`FusedSegment`.

        A segment grows from an entry unit by repeatedly absorbing any
        fusable unit ALL of whose predecessors are already members —
        this admits fan-out and fan-in (InputJoiner diamonds) inside
        the segment, not just linear chains, while keeping execution
        correct: only the entry has edges from outside, so when the
        scheduler releases the entry every member's inputs exist, and
        the grow order is a topological order of the region (each
        member was added after all its predecessors)."""
        self._segments_ = []

        def fusable(u):
            return isinstance(u, AcceleratedUnit) and u.FUSABLE

        accel = [u for u in self.units if fusable(u)]
        accel_set = set(accel)
        # visit candidate entries in TOPOLOGICAL order of the fusable
        # subgraph — unit insertion order is not reliable (a unit
        # linked before its predecessor was created would otherwise
        # become an entry and strand that predecessor unfused).  Kahn;
        # cycle remainders (only possible via gated loops) keep
        # insertion order.
        indeg = {u: sum(1 for p in u.links_from if p in accel_set)
                 for u in accel}
        ready = [u for u in accel if indeg[u] == 0]
        topo = []
        while ready:
            u = ready.pop(0)
            topo.append(u)
            for v in u.links_to:
                if v in indeg:
                    indeg[v] -= 1
                    if indeg[v] == 0:
                        ready.append(v)
        done = set(topo)
        topo += [u for u in accel if u not in done]
        in_segment = set()

        for entry in topo:
            if entry in in_segment:
                continue
            members = [entry]
            member_set = {entry}
            grown = True
            while grown:
                grown = False
                # scan the frontier: successors of members whose every
                # predecessor is already inside
                for m in list(members):
                    for v in m.links_to:
                        if (v in accel_set and v not in member_set
                                and v not in in_segment
                                and v.links_from
                                and all(p in member_set
                                        for p in v.links_from)):
                            members.append(v)
                            member_set.add(v)
                            grown = True
            if len(members) > 1:
                in_segment |= member_set
                seg = FusedSegment(members)
                for member in members:
                    member._segment_ = seg
                self._segments_.append(seg)
        if self._segments_:
            self.debug("fused %d segment(s): %s", len(self._segments_),
                       self._segments_)
        return self._segments_

    @property
    def computing_power(self):
        """Device rating for the elastic coordinator handshake
        (ref: veles/accelerated_units.py:843-858)."""
        return self.device.compute_power() if self.device else 0.0


class DeviceBenchmark(AcceleratedUnit):
    """Unit exposing the GEMM roofline probe in-graph
    (ref: veles/accelerated_units.py:706)."""

    FUSABLE = False  # no step(); runs host-side at initialize

    def __init__(self, workflow, **kwargs):
        super(DeviceBenchmark, self).__init__(workflow, **kwargs)
        self.computing_power = 0.0

    def initialize(self, device=None, **kwargs):
        super(DeviceBenchmark, self).initialize(device=device, **kwargs)
        if self.device is not None:
            self.computing_power = self.device.compute_power()

    def run(self):
        pass
