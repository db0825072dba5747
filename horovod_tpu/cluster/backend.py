"""Execution backends (reference: ``horovod/spark/common/backend.py:90`` —
``Backend.run(fn)`` abstracts where the per-rank training processes live:
Spark tasks there; here in-process device-rank threads or hvdrun-launched
OS processes.  A Spark/K8s backend is a subclass implementing ``run``)."""


class Backend:
    def num_processes(self):
        raise NotImplementedError

    def run(self, fn, args=(), kwargs=None):
        """Run ``fn(rank, *args, **kwargs)`` once per rank; return the list
        of per-rank results (rank order)."""
        raise NotImplementedError


class InProcessBackend(Backend):
    """Device-rank threads inside this process (the 8-device CPU-mesh test
    topology, or one TPU host's chips)."""

    def __init__(self, num_proc=None):
        self._num_proc = num_proc

    def num_processes(self):
        import horovod_tpu as hvd

        if self._num_proc is not None:
            from horovod_tpu.common import basics

            if basics._state is None:
                # restrict the rank set to num_proc devices BEFORE the
                # first init: the threaded eager path would otherwise
                # wait forever for device ranks that have no training
                # thread
                import jax

                devices = list(jax.devices())
                if self._num_proc < len(devices):
                    hvd.init(comm=devices[:self._num_proc])
                else:
                    hvd.init()
            else:
                hvd.init()  # no-op; verify compatibility below
            if hvd.size() != self._num_proc:
                raise RuntimeError(
                    f"InProcessBackend(num_proc={self._num_proc}) but "
                    f"horovod_tpu is initialized with {hvd.size()} "
                    f"ranks; shut down first or match num_proc")
            return self._num_proc
        hvd.init()
        return hvd.local_size()

    def run(self, fn, args=(), kwargs=None):
        from horovod_tpu.common import basics

        kwargs = kwargs or {}
        return basics.run_parallel(
            lambda rank: fn(rank, *args, **kwargs),
            num_ranks=self.num_processes())


class ProcessBackend(Backend):
    """One OS process per rank through the programmatic launcher
    (reference analog: ``horovod.spark.run`` driving task processes;
    here ``horovod_tpu.run.run``)."""

    def __init__(self, num_proc, hosts=None, extra_env=None,
                 jax_platform=None):
        self._num_proc = num_proc
        self._hosts = hosts
        self._extra_env = extra_env
        self._jax_platform = jax_platform

    def num_processes(self):
        return self._num_proc

    def run(self, fn, args=(), kwargs=None):
        from horovod_tpu.run import run as hvd_run

        platform = self._jax_platform

        def wrapper(*a, **kw):
            if platform is not None:
                # must happen before hvd.init() touches jax
                import jax

                jax.config.update("jax_platforms", platform)
            import horovod_tpu as hvd

            hvd.init()
            try:
                return fn(hvd.rank(), *a, **kw)
            finally:
                hvd.shutdown()

        return hvd_run(wrapper, args=args, kwargs=kwargs or {},
                       np=self._num_proc, hosts=self._hosts,
                       extra_env=self._extra_env)
