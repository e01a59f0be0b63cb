"""REST model serving workflow — load a trained snapshot and serve its
forward chain over HTTP (the reference paired RestfulLoader with the
RESTfulAPI unit the same way; veles/restful_api.py:78).

    python -m veles_tpu veles_tpu/samples/serve.py \
        -c "root.serve.snapshot='snapshots/mnist_current.pickle.gz'" \
        -c "root.serve.workflow='veles_tpu/samples/mnist.py'" \
        -c "root.serve.port=8080"

    curl -X POST http://localhost:8080/api \
         -d '{"input": [0.0, 0.1, ...]}'
    curl -X POST http://localhost:8080/generate \
         -d '{"prompt": [3, 1, 4], "steps": 32}'  # LM snapshots only
    curl -X POST http://localhost:8080/shutdown   # clean stop

Without a snapshot, ``root.serve.layers`` (a ``layers`` spec as
``models/standard.make_forwards`` takes it, e.g. an embedding, some
``lfm2_block`` or ``solar_block`` and a ``rms_token_logits``) builds the chain with the
units' own seeded filling, and ``root.serve.window`` bounds a request
where no positional table does (rotary positions).

Graph: repeater → restful_loader → [forwards from the snapshot] → api,
looping until /shutdown (or the feed closes).
"""

from veles_tpu.accelerated_units import AcceleratedWorkflow
from veles_tpu.config import root
from veles_tpu.mutable import Bool
from veles_tpu.plumbing import Repeater
from veles_tpu.restful_api import RESTfulAPI, RestfulLoader


class _ServingLoader(RestfulLoader):
    """RestfulLoader that publishes idle/closed state as gate Bools."""

    def __init__(self, workflow, **kwargs):
        super(_ServingLoader, self).__init__(workflow, **kwargs)
        #: True while the last serve produced no samples — the forward
        #: chain is gate-skipped on idle waves (no wasted device work)
        self.idle = Bool(False, "idle")
        self.stop_requested = Bool(False, "stop_requested")

    def run(self):
        super(_ServingLoader, self).run()
        self.idle.set(self.minibatch_size == 0)
        if self.closed:
            self.stop_requested.set(True)


class ServeWorkflow(AcceleratedWorkflow):
    def __init__(self, workflow, **kwargs):
        super(ServeWorkflow, self).__init__(workflow, name="Serve",
                                            **kwargs)
        cfg = root.serve
        snapshot, layers = cfg.get("snapshot"), cfg.get("layers")
        window = cfg.get("window")
        if snapshot:
            from veles_tpu.snapshotter import SnapshotterToFile
            # a CLI-trained snapshot pickles classes under the workflow
            # FILE's module name ('lm', 'mnist', …) — that module must
            # be importable here before unpickling (the reference
            # resumed through the same re-import,
            # veles/__main__.py:539-589)
            wf_file = cfg.get("workflow")
            if wf_file:
                from veles_tpu.import_file import import_file_as_module
                import_file_as_module(wf_file)
            trained = SnapshotterToFile.import_file(snapshot)
            self.forwards = trained.forwards  # adopted trained chain
            sample_shape = tuple(
                trained.loader.minibatch_data.shape[1:])
        elif layers:
            sample_shape = (int(window or 1024),)
        else:
            raise ValueError(
                "set root.serve.snapshot to a trained workflow "
                "snapshot, or root.serve.layers to a layer spec")

        self.repeater = Repeater(self)
        self.repeater.link_from(self.start_point)
        self.loader = _ServingLoader(
            self, sample_shape=sample_shape,
            minibatch_size=int(cfg.get("minibatch_size", 16)),
            max_wait=float(cfg.get("max_wait", 1.0)))
        self.loader.link_from(self.repeater)
        if not snapshot:
            from veles_tpu.models.standard import make_forwards
            self.forwards = make_forwards(
                self, self.loader.minibatch_data,
                [dict(spec) for spec in layers])

        prev = self.loader.minibatch_data
        for u in self.forwards:
            u.unlink_all()           # drop the training graph's wiring
            u.workflow = self        # re-home the adopted units
            u.input = prev
            u.gate_skip = self.loader.idle
            prev = u.output
        self.forwards[0].link_from(self.loader)
        for a, b in zip(self.forwards, self.forwards[1:]):
            b.link_from(a)

        from veles_tpu.models.lfm2 import NormedTokenLogits
        from veles_tpu.models.transformer import TokenProjection
        self.api = RESTfulAPI(
            self, loader=self.loader,
            port=int(cfg.get("port", 0)),
            host=cfg.get("host", "127.0.0.1"),
            # continuous-batching knobs (docs/serving.md): slots,
            # queue cap and the off switch ride root.serve
            serving=bool(cfg.get("serving", True)),
            max_slots=int(cfg.get("max_slots", 4)),
            max_queue=int(cfg.get("max_queue", 32)),
            serving_window=int(window) if window else None,
            # an LM chain (per-token logits head) also serves
            # POST /generate — autoregressive decode off the same chain
            forwards=self.forwards
            if isinstance(self.forwards[-1],
                          (TokenProjection, NormedTokenLogits))
            else None)
        self.api.output = self.forwards[-1].output
        self.api.gate_skip = self.loader.idle
        self.api.shutdown_callback = self.request_stop
        self.api.link_from(self.forwards[-1])

        # the serving loop mirrors the training graph's termination
        # handshake: stop_requested blocks the loader and opens the end
        self.repeater.link_from(self.api)
        self.loader.gate_block = self.loader.stop_requested
        self.end_point.link_from(self.api)
        self.end_point.gate_block = ~self.loader.stop_requested

    def initialize(self, **kwargs):
        super(ServeWorkflow, self).initialize(**kwargs)
        # adopted forwards keep their trained weights (the any-PARAMS
        # refill guard skips restored params)
        self.info("serving on http://%s:%d/api (POST {\"input\": ...}; "
                  "POST /shutdown to stop)", self.api.host, self.api.port)

    def request_stop(self):
        """Thread-safe stop: close the feed; the next wave terminates
        the loop through the gates."""
        self.loader.stop_requested.set(True)
        self.loader.close()

    def run(self):
        try:
            super(ServeWorkflow, self).run()
        finally:
            self.api.stop()

    def stop(self):
        self.request_stop()
        super(ServeWorkflow, self).stop()


def run(load, main):
    load(ServeWorkflow)
    main()
