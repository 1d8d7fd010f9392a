// stablehlo_runner: a NON-PYTHON consumer of the framework's exported
// inference artifact (reference capability: the C++ predictor + C API,
// inference/api/paddle_api.h, api/api_impl.cc NativePaddlePredictor, and
// the C++-only train/infer demo inference/train/demo/demo_trainer.cc).
//
// TPU-native form: the export is StableHLO (inference/export.py
// export_stablehlo) and the runtime is any PJRT plugin — this program
// dlopens a PJRT C-API plugin (e.g. the TPU plugin), compiles the
// StableHLO module, uploads the manifest-described input tensors, runs,
// and writes raw output tensors. No Python anywhere in the serving path.
//
// Usage:
//   stablehlo_runner <pjrt_plugin.so> <bundle_dir>
// where <bundle_dir> contains (written by export.write_runner_bundle):
//   model.stablehlo        StableHLO module text
//   compile_options.pb     serialized xla.CompileOptionsProto
//   manifest.txt           lines: "input <name> <dtype> <rank> <dims...>
//                          <file>" in the executable's argument order
// outputs land in <bundle_dir>/out_<i>.bin (raw bytes, row-major).

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

[[noreturn]] void Die(const std::string& msg) {
  std::fprintf(stderr, "stablehlo_runner: %s\n", msg.c_str());
  std::exit(1);
}

const PJRT_Api* g_api = nullptr;

void Check(PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  Die(std::string(what) + ": " + msg);
}

void AwaitEvent(PJRT_Event* event, const char* what) {
  PJRT_Event_Await_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  args.event = event;
  Check(g_api->PJRT_Event_Await(&args), what);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = event;
  g_api->PJRT_Event_Destroy(&dargs);
}

std::string ReadFile(const std::string& path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) Die("cannot open " + path);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

struct InputSpec {
  std::string name;
  PJRT_Buffer_Type type;
  std::vector<int64_t> dims;
  std::string data;
};

PJRT_Buffer_Type ParseType(const std::string& t) {
  if (t == "float32") return PJRT_Buffer_Type_F32;
  if (t == "int32") return PJRT_Buffer_Type_S32;
  if (t == "int64") return PJRT_Buffer_Type_S64;
  if (t == "bfloat16") return PJRT_Buffer_Type_BF16;
  Die("unsupported dtype " + t);
}

}  // namespace

int main(int argc, char** argv) {
  if (argc != 3) Die("usage: stablehlo_runner <pjrt_plugin.so> <bundle_dir>");
  const std::string plugin_path = argv[1];
  const std::string dir = argv[2];

  void* lib = dlopen(plugin_path.c_str(), RTLD_NOW | RTLD_GLOBAL);
  if (!lib) Die(std::string("dlopen: ") + dlerror());
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(lib, "GetPjrtApi"));
  if (!get_api) Die("plugin has no GetPjrtApi symbol");
  g_api = get_api();
  std::fprintf(stderr, "PJRT plugin API v%d.%d (runner built for v%d.%d)\n",
               g_api->pjrt_api_version.major_version,
               g_api->pjrt_api_version.minor_version, PJRT_API_MAJOR,
               PJRT_API_MINOR);

  {
    PJRT_Plugin_Initialize_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    Check(g_api->PJRT_Plugin_Initialize(&args), "Plugin_Initialize");
  }

  // plugin create options from <bundle_dir>/options.txt, lines of
  //   i <name> <int64>     |     s <name> <string>
  // (optional: a missing file means no options)
  std::vector<std::string> opt_names, opt_strs;
  std::vector<int64_t> opt_ints;
  std::vector<char> opt_kinds;
  {
    std::ifstream of(dir + "/options.txt");
    std::string kind, name;
    while (of >> kind >> name) {
      opt_kinds.push_back(kind[0]);
      opt_names.push_back(name);
      if (kind == "i") {
        int64_t v;
        of >> v;
        opt_ints.push_back(v);
        opt_strs.push_back("");
      } else {
        std::string v;
        of >> v;
        opt_strs.push_back(v);
        opt_ints.push_back(0);
      }
    }
  }
  std::vector<PJRT_NamedValue> named(opt_names.size());
  for (size_t i = 0; i < opt_names.size(); ++i) {
    std::memset(&named[i], 0, sizeof(PJRT_NamedValue));
    named[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
    named[i].name = opt_names[i].c_str();
    named[i].name_size = opt_names[i].size();
    if (opt_kinds[i] == 'i') {
      named[i].type = PJRT_NamedValue_kInt64;
      named[i].int64_value = opt_ints[i];
      named[i].value_size = 1;
    } else {
      named[i].type = PJRT_NamedValue_kString;
      named[i].string_value = opt_strs[i].c_str();
      named[i].value_size = opt_strs[i].size();
    }
  }

  PJRT_Client* client = nullptr;
  {
    PJRT_Client_Create_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    args.create_options = named.data();
    args.num_options = named.size();
    Check(g_api->PJRT_Client_Create(&args), "Client_Create");
    client = args.client;
  }

  PJRT_Device* device = nullptr;
  {
    PJRT_Client_AddressableDevices_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    args.client = client;
    Check(g_api->PJRT_Client_AddressableDevices(&args),
          "AddressableDevices");
    if (args.num_addressable_devices == 0) Die("no addressable devices");
    device = args.addressable_devices[0];
  }

  // ---- compile the StableHLO module
  std::string module_text = ReadFile(dir + "/model.stablehlo");
  std::string compile_options = ReadFile(dir + "/compile_options.pb");
  PJRT_LoadedExecutable* exec = nullptr;
  {
    PJRT_Program program;
    std::memset(&program, 0, sizeof(program));
    program.struct_size = PJRT_Program_STRUCT_SIZE;
    program.code = module_text.data();
    program.code_size = module_text.size();
    static const char kFormat[] = "mlir";
    program.format = kFormat;
    program.format_size = sizeof(kFormat) - 1;

    PJRT_Client_Compile_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    args.client = client;
    args.program = &program;
    args.compile_options = compile_options.data();
    args.compile_options_size = compile_options.size();
    Check(g_api->PJRT_Client_Compile(&args), "Client_Compile");
    exec = args.executable;
  }

  // ---- upload inputs per the manifest
  std::vector<InputSpec> inputs;
  {
    std::ifstream mf(dir + "/manifest.txt");
    if (!mf) Die("cannot open manifest.txt");
    std::string kind;
    while (mf >> kind) {
      if (kind != "input") Die("manifest: unexpected entry " + kind);
      InputSpec spec;
      std::string dtype, file;
      size_t rank;
      mf >> spec.name >> dtype >> rank;
      spec.type = ParseType(dtype);
      spec.dims.resize(rank);
      for (size_t i = 0; i < rank; ++i) mf >> spec.dims[i];
      mf >> file;
      spec.data = ReadFile(dir + "/" + file);
      inputs.push_back(std::move(spec));
    }
  }

  std::vector<PJRT_Buffer*> arg_buffers;
  for (const InputSpec& spec : inputs) {
    PJRT_Client_BufferFromHostBuffer_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    args.client = client;
    args.data = spec.data.data();
    args.type = spec.type;
    args.dims = spec.dims.data();
    args.num_dims = spec.dims.size();
    args.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    args.device = device;
    Check(g_api->PJRT_Client_BufferFromHostBuffer(&args),
          ("BufferFromHostBuffer " + spec.name).c_str());
    AwaitEvent(args.done_with_host_buffer, "host buffer transfer");
    arg_buffers.push_back(args.buffer);
  }

  // ---- execute
  size_t num_outputs = 0;
  {
    PJRT_LoadedExecutable_GetExecutable_Args gargs;
    std::memset(&gargs, 0, sizeof(gargs));
    gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    gargs.loaded_executable = exec;
    Check(g_api->PJRT_LoadedExecutable_GetExecutable(&gargs),
          "GetExecutable");
    PJRT_Executable_NumOutputs_Args nargs;
    std::memset(&nargs, 0, sizeof(nargs));
    nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    nargs.executable = gargs.executable;
    Check(g_api->PJRT_Executable_NumOutputs(&nargs), "NumOutputs");
    num_outputs = nargs.num_outputs;
  }

  std::vector<PJRT_Buffer*> out_buffers(num_outputs, nullptr);
  {
    PJRT_ExecuteOptions options;
    std::memset(&options, 0, sizeof(options));
    options.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_Buffer* const* arg_list = arg_buffers.data();
    PJRT_Buffer** out_list = out_buffers.data();
    PJRT_Event* device_complete = nullptr;

    PJRT_LoadedExecutable_Execute_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    args.executable = exec;
    args.options = &options;
    args.argument_lists = &arg_list;
    args.num_devices = 1;
    args.num_args = arg_buffers.size();
    args.output_lists = &out_list;
    args.device_complete_events = &device_complete;
    Check(g_api->PJRT_LoadedExecutable_Execute(&args), "Execute");
    AwaitEvent(device_complete, "device execution");
  }

  // ---- fetch outputs to host, write raw files
  for (size_t i = 0; i < num_outputs; ++i) {
    PJRT_Buffer_ToHostBuffer_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    args.src = out_buffers[i];
    Check(g_api->PJRT_Buffer_ToHostBuffer(&args), "ToHostBuffer size");
    std::vector<char> host(args.dst_size);
    args.dst = host.data();
    Check(g_api->PJRT_Buffer_ToHostBuffer(&args), "ToHostBuffer copy");
    AwaitEvent(args.event, "device-to-host copy");
    std::string out_path = dir + "/out_" + std::to_string(i) + ".bin";
    std::ofstream f(out_path, std::ios::binary);
    f.write(host.data(), static_cast<std::streamsize>(host.size()));
    std::fprintf(stderr, "wrote %s (%zu bytes)\n", out_path.c_str(),
                 host.size());
  }
  std::printf("OK %zu outputs\n", num_outputs);
  return 0;
}
