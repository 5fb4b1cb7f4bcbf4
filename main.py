"""CLI entry point — mode dispatch over the config.yaml in the CWD.

JAX picks the platform: the accelerator when one is attached, or what
``JAX_PLATFORMS`` says (e.g. a virtual CPU mesh: ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Same command surface as the reference (/root/reference/main.py:19-36):
  --train / -t           local training (learner + local workers)
  --train-server / -ts   learner serving remote worker machines
  --worker / -w          worker machine joining a train server
  --eval / -e            offline evaluation of a saved model
  --eval-server / -es    network battle server
  --eval-client / -ec    network battle client
"""

import sys

import yaml


def main():
    from handyrl_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    with open("config.yaml") as f:
        args = yaml.safe_load(f)
    print(args)

    if len(sys.argv) < 2:
        print("Please set a mode (--train, --train-server, --worker, "
              "--eval, --eval-server, --eval-client).")
        sys.exit(1)

    mode = sys.argv[1]
    argv = sys.argv[2:]

    if mode in ("--train", "-t"):
        from handyrl_tpu.learner import train_main

        train_main(args)
    elif mode in ("--train-server", "-ts"):
        from handyrl_tpu.learner import train_server_main

        train_server_main(args)
    elif mode in ("--worker", "-w"):
        from handyrl_tpu.worker import worker_main

        worker_main(args, argv)
    elif mode in ("--eval", "-e"):
        from handyrl_tpu.evaluation import eval_main

        eval_main(args, argv)
    elif mode in ("--eval-server", "-es"):
        from handyrl_tpu.evaluation import eval_server_main

        eval_server_main(args, argv)
    elif mode in ("--eval-client", "-ec"):
        from handyrl_tpu.evaluation import eval_client_main

        eval_client_main(args, argv)
    else:
        print(f"Unknown mode {mode}.")
        sys.exit(1)


if __name__ == "__main__":
    main()
